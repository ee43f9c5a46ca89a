"""Plain float32 reference of the federated runs the benchmark times.

It imports nothing of the program and takes nothing the program made: it
draws its own parameters, selections, latencies and local batches from
the run's seed with the same ``jax.random`` calls the semantics name. The
model (parameters, a batch's loss, the eval) is the configuration's model
module's (``bench/models``), whose matrix products run at JAX's default
precision, the one both configurations state for the program's; here are
only the federated semantics, whose weighted sums are float32
(``Precision.HIGHEST``). Written for
reading, not speed: straightforward ``jax.numpy``, one client at a time
inside a block (``vmap`` over a block of clients, a loop over blocks,
so only one block of parameter copies is alive).

The semantics it follows, per server step ``r`` (the run key is the third
of three splits of ``PRNGKey(seed)``; step ``r`` uses
``split(fold_in(run_key, r))`` = ``(k_sel, k_local)``):

- admission (the paper's Markov policy, Theorem 2 of arXiv 2408.00217):
  client ``i`` wants the model when ``uniform(k_sel)[i] < p[min(age_i, m)]``;
  ages start from the chain's stationary law and follow
  ``A' = (A + 1)(1 - S)``;
- sync (FedAvg): the first ``width`` wanting clients in index order train
  from the global params; the new params are their mean;
- async (FedBuff): idle, available wanting clients are dispatched with a
  latency ``speed * exp(mu + sigma N) + shift + Exp / rate``; the ``B``
  earliest completions (ties to the lower index) train from the model
  version they were dispatched with (the oldest of the last ``H`` when
  older) and are aggregated as a ``(1 + staleness)^-a``-weighted mean of
  deltas added to the global params;
- local training: ``E`` epochs of SGD over a fresh permutation of the
  client's examples each epoch, ``lr = lr0 * decay^t``.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# clients trained together inside one block of the cohort, at most
CLIENT_BLOCK = 125
# device bytes a block may hold in parameter copies: a client in a block
# keeps about four (its start, its trained params, a gradient, its delta)
BLOCK_BYTES = 4 * 2**30
I32_MAX = np.iinfo(np.int32).max


# ---------------------------------------------------------------- training


def local_train(loss_fn, p, xs, ys, key, lr, epochs: int, batch: int):
    """``epochs`` passes of SGD on ``loss_fn`` over one client's examples."""
    examples = xs.shape[0]
    nb, bs = max(examples // batch, 1), min(batch, examples)
    perms = jax.vmap(
        lambda k: jax.random.permutation(k, examples)[:nb * bs].reshape(nb, bs)
    )(jax.random.split(key, epochs)).reshape(epochs * nb, bs)

    def sgd(p, idx):
        loss, g = jax.value_and_grad(loss_fn)(p, xs[idx], ys[idx])
        return jax.tree.map(lambda a, b: (a - lr * b).astype(a.dtype), p, g), loss

    p, losses = jax.lax.scan(sgd, p, perms)
    return p, losses.mean()


def _cast(a, dtype):
    """Floating arrays in ``dtype``; integer ones (labels, tokens) as
    they are."""
    return a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a


@functools.partial(jax.jit, static_argnums=1)
def _rows(a, dtype):
    """One row per client: ``(n, per_client, ...)`` -> ``(n, -1)``."""
    return _cast(a.reshape(a.shape[0], -1), dtype)


# ---------------------------------------------------------------- admission


def markov_probs(n: int, k: int, m: int) -> np.ndarray:
    """Theorem 2's optimal p_0..p_m for E[X] = n/k."""
    r = n / k
    i = math.floor(r)
    p = np.zeros(m + 1)
    if m <= i - 1:
        p[m] = 1.0 / (r - m)
    else:
        if i >= 1:
            p[i - 1] = (i + 1) - r
        p[i:] = 1.0
    return p.astype(np.float32)


def stationary(p) -> np.ndarray:
    """Stationary law of the age chain with send probabilities ``p``."""
    p = np.asarray(p, np.float64)
    m = len(p) - 1
    w = np.ones(m + 1)
    for i in range(1, m + 1):
        w[i] = w[i - 1] * (1.0 - p[i - 1])
    w[m] /= p[m]
    return w / w.sum()


def cohort_width(n: int, k: int) -> int:
    """The cohort buffer: k plus four binomial standard deviations."""
    q = k / n
    return min(n, int(k + 4 * math.sqrt(n * q * (1 - q))) + 1)


def run_keys(seed, n: int, m: int, pi):
    """A run seed's (init key, starting ages, run key): the ages are drawn
    from the chain's stationary law ``pi``."""
    k_init, k_policy, k_run = jax.random.split(jax.random.PRNGKey(seed), 3)
    ages = jax.random.choice(k_policy, m + 1, shape=(n,), p=pi)
    return k_init, ages.astype(jnp.int32), k_run


def want(k_sel, ages, p, m: int):
    """The clients that want the model this step."""
    return jax.random.uniform(k_sel, ages.shape) < p[jnp.minimum(ages, m)]


def cohort_sizes(config: dict, traffic: dict, run_seeds, steps: int):
    """Each run seed's sync cohort size (clients that want the model, at
    most the cohort width) in each of its first ``steps`` rounds, as an
    array (seeds, steps). Admission alone decides it: training does not
    feed back into it."""
    run = {**config["run"], **traffic["run"]}
    n, k, m = run["n_clients"], run["k"], run["m"]
    p = markov_probs(n, k, m)
    pi = jnp.asarray(stationary(p).astype(np.float32))
    p, width = jnp.asarray(p), cohort_width(n, k)

    def one(seed):
        _, ages, k_run = run_keys(seed, n, m, pi)

        def step(ages, r):
            k_sel, _ = jax.random.split(jax.random.fold_in(k_run, r))
            sel = want(k_sel, ages, p, m)
            return ((ages + 1) * (1 - sel.astype(jnp.int32)),
                    jnp.minimum(sel.sum(), width))

        return jax.lax.scan(step, ages, jnp.arange(steps))[1]

    seeds = jnp.asarray(np.asarray(run_seeds, np.int64), jnp.int32)
    return np.asarray(jax.jit(jax.vmap(one))(seeds))


def _accumulate_gaps(acc, sel, r):
    """Selection gaps X = r - (step of the client's last selection)."""
    has = sel & (acc["last_sel"] >= 0)
    gap = jnp.where(has, r - acc["last_sel"], 0)
    return {
        "last_sel": jnp.where(sel, r, acc["last_sel"]),
        "gap_sum": acc["gap_sum"] + gap.sum(),
        "gap_sumsq": acc["gap_sumsq"] + (gap.astype(jnp.float32) ** 2).sum(),
        "gap_cnt": acc["gap_cnt"] + has.sum(),
    }


# ---------------------------------------------------------------- runs


def client_block(params) -> int:
    """Clients the reference trains together: as many as keep four copies
    of ``params`` a client within ``BLOCK_BYTES``, from 1 to
    ``CLIENT_BLOCK``."""
    size = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    return max(1, min(CLIENT_BLOCK, BLOCK_BYTES // (4 * size)))


class Reference:
    """One configuration's reference run from a seed.

    ``model`` is the configuration's model module (``bench.models``): its
    ``init``, ``loss`` and ``eval_loss``. ``dtype`` is the type parameters,
    data and arithmetic are held in: float32 for the reference, bfloat16
    for its control. It trains ``client_block(params)`` clients at a
    time: a model whose copies the chip holds few of trains one by one."""

    def __init__(self, model, config: dict, traffic: dict, data, seed: int,
                 dtype=jnp.float32):
        self.model = model
        self.run = {**config["run"], **traffic["run"]}
        self.latency = config.get("latency", {})
        self.dtype = dtype
        # arguments of the jitted programs: closed over, XLA would embed
        # the fleet's data as a constant. One row per client, so a block's
        # gather reads its rows and relays out nothing else
        self.data = {"x": _rows(data.x, dtype), "y": _rows(data.y, dtype)}
        self.shapes = {k: getattr(data, k).shape[1:] for k in ("x", "y")}
        self.test = jax.tree.map(lambda a: _cast(a, dtype), data.test)
        run = self.run
        if run["policy"] != "markov":
            raise ValueError(f"the reference follows the markov policy only, "
                             f"not {run['policy']!r}")
        self.n, self.k, self.m = run["n_clients"], run["k"], run["m"]
        p = markov_probs(self.n, self.k, self.m)
        self.p = jnp.asarray(p)
        self.pi = jnp.asarray(stationary(p).astype(np.float32))
        k_init, self.ages0, self.k_run = run_keys(seed, self.n, self.m,
                                                   self.pi)
        self.params0 = model.init(k_init, config, dtype)
        self.client_block = client_block(self.params0)
        self.sync = run.get("mode", "sync") == "sync"
        self._chunk = jax.jit(self._sync_chunk if self.sync
                              else self._async_chunk, static_argnums=3)
        self._eval = jax.jit(model.eval_loss)

    def _train(self, params_of, data, idx, keys, lrs, weights):
        """Weighted sum over the cohort of (trained params - start params)
        and of the local losses, training ``client_block`` clients at a
        time. ``params_of(j)`` is slot ``j``'s start params; ``data`` holds
        each client's examples as one row. A loop trains the blocks up to
        the last slot of nonzero weight and skips the rest, whose slots
        would add nothing to either sum."""
        width = idx.shape[0]
        blk = min(self.client_block, width)
        pad = -width % blk
        slots = jnp.arange(width + pad).reshape(-1, blk)

        def one(j):
            j = jnp.minimum(j, width - 1)
            start = params_of(j)
            xs, ys = (data[k][idx[j]].reshape(self.shapes[k]) for k in ("x", "y"))
            got, loss = local_train(self.model.loss, start, xs, ys, keys[j],
                                    lrs[j], self.run["local_epochs"],
                                    self.run["batch_size"])
            return jax.tree.map(lambda a, b: a - b, got, start), loss

        def body(acc, js):
            deltas, losses = jax.vmap(one)(js)
            w = jnp.where(js < width, weights[jnp.minimum(js, width - 1)], 0.0)
            dsum = jax.tree.map(
                lambda s, d: s + jnp.tensordot(w.astype(d.dtype), d, axes=1,
                                               precision=HIGHEST), acc[0], deltas)
            return dsum, acc[1] + (w * losses.astype(jnp.float32)).sum()

        zero = (jax.tree.map(jnp.zeros_like, params_of(0)),
                jnp.zeros((), jnp.float32))
        used = jnp.max(jnp.where(weights != 0, jnp.arange(width) + 1, 0))
        return jax.lax.fori_loop(0, -(-used // blk),
                                 lambda b, acc: body(acc, slots[b]), zero)

    def lr(self, t):
        return (jnp.asarray(self.run["lr0"], jnp.float32)
                * self.run["lr_decay"] ** t.astype(jnp.float32))

    def _want(self, k_sel, ages):
        return want(k_sel, ages, self.p, self.m)

    def init_state(self) -> Dict:
        st = {
            # an argument of the jitted chunk, not a constant: a constant
            # that differs by seed would compile the chunk for every seed
            "k_run": self.k_run,
            "params": self.params0, "ages": self.ages0,
            "acc": {"last_sel": jnp.full((self.n,), -1, jnp.int32),
                    "gap_sum": jnp.zeros((), jnp.int32),
                    "gap_sumsq": jnp.zeros((), jnp.float32),
                    "gap_cnt": jnp.zeros((), jnp.int32)},
        }
        if self.sync:
            return st
        H = self.run["max_versions"]
        lat = self.latency
        speed = jnp.ones((self.n,), jnp.float32)
        if lat.get("hetero", 0) > 0:
            speed = jnp.exp(lat["hetero"] * jax.random.normal(
                jax.random.fold_in(self.k_run, 2**31), (self.n,), jnp.float32))
        st.update({
            "hist": jax.tree.map(lambda a: jnp.stack([a] * H), self.params0),
            "t_done": jnp.full((self.n,), jnp.inf, jnp.float32),
            "disp_ver": jnp.full((self.n,), -1, jnp.int32),
            "next_avail": jnp.zeros((self.n,), jnp.float32),
            "dropped": jnp.zeros((self.n,), bool),
            "last_done": jnp.full((self.n,), -1.0, jnp.float32),
            "speed": speed,
            "clock": jnp.zeros((), jnp.float32),
            "version": jnp.zeros((), jnp.int32),
        })
        return st

    # one sync round (FedAvg over the padded cohort's real members)
    def _sync_chunk(self, st, data, r0, length):
        width = cohort_width(self.n, self.k)

        def step(st, r):
            k_sel, k_local = jax.random.split(jax.random.fold_in(st["k_run"], r))
            sel = self._want(k_sel, st["ages"])
            idx = jnp.nonzero(sel, size=width, fill_value=-1)[0]
            mask = (idx >= 0).astype(jnp.float32)
            idx = jnp.maximum(idx, 0)
            keys = jax.random.split(k_local, width)
            lrs = jnp.broadcast_to(self.lr(r), (width,))
            g = st["params"]
            dsum, lsum = self._train(lambda j: g, data, idx, keys, lrs, mask)
            wsum = mask.sum()
            new = jax.tree.map(
                lambda a, d: jnp.where(wsum > 0, a + (d / jnp.maximum(wsum, 1.0)).astype(a.dtype), a),
                g, dsum)
            loss = jnp.where(wsum > 0, lsum / jnp.maximum(wsum, 1.0), jnp.nan)
            st = {"k_run": st["k_run"], "params": new,
                  "ages": (st["ages"] + 1) * (1 - sel.astype(jnp.int32)),
                  "acc": _accumulate_gaps(st["acc"], sel, r)}
            return st, {"loss": loss, "sel": sel}

        return jax.lax.scan(step, st, r0 + jnp.arange(length))

    # one async server step (FedBuff over the B earliest completions)
    def _async_chunk(self, st, data, r0, length):
        run, lat = self.run, self.latency
        B, H = run["buffer_size"], run["max_versions"]
        n = self.n

        def step(st, r):
            k_sel, k_local = jax.random.split(jax.random.fold_in(st["k_run"], r))
            clock, version = st["clock"], st["version"]
            send = (self._want(k_sel, st["ages"]) & jnp.isinf(st["t_done"])
                    & (st["next_avail"] <= clock))
            k_c, k_t = jax.random.split(jax.random.fold_in(k_sel, 101))
            compute = jnp.exp(lat.get("compute_mu", 0.0) + lat["compute_sigma"]
                              * jax.random.normal(k_c, (n,), jnp.float32))
            comm = lat["comm_shift"] + jax.random.exponential(
                k_t, (n,), jnp.float32) / lat["comm_rate"]
            latency = st["speed"] * compute + comm
            dropped = jnp.zeros((n,), bool)
            if lat.get("dropout", 0) > 0:
                dropped = jax.random.uniform(
                    jax.random.fold_in(k_sel, 102), (n,)) < lat["dropout"]
            t_done = jnp.where(send, clock + latency, st["t_done"])
            disp_ver = jnp.where(send, version, st["disp_ver"])
            dropped = jnp.where(send, dropped, st["dropped"])
            # the B earliest completions, ties to the lower client index
            neg, idx = jax.lax.top_k(-t_done, B)
            t = -neg
            valid = jnp.isfinite(t)
            idx = jnp.where(valid, idx, 0)
            out = jnp.where(valid, idx, I32_MAX)
            t_done = t_done.at[out].set(jnp.inf, mode="drop")
            new_clock = jnp.maximum(clock, jnp.max(jnp.where(valid, t, -jnp.inf)))
            new_clock = jnp.where(valid.any(), new_clock, jnp.maximum(
                new_clock, jnp.min(st["next_avail"])))
            dv = disp_ver[idx]
            read = jnp.clip(dv, jnp.maximum(version - (H - 1), 0), version) % H
            keys = jax.random.split(k_local, B)
            lrs = self.lr(jnp.maximum(dv, 0))
            succ = valid & ~dropped[idx]
            stale = jnp.maximum(version - dv, 0).astype(jnp.float32)
            w = succ.astype(jnp.float32) * (1.0 + stale) ** (
                -run.get("aggregator_kwargs", {}).get("staleness_exp", 0.5))
            hist = st["hist"]
            dsum, lsum = self._train(
                lambda j: jax.tree.map(lambda h: h[read[j]], hist), data,
                idx, keys, lrs, w)
            wsum = w.sum()
            has = wsum > 0
            params = jax.tree.map(
                lambda a, d: jnp.where(has, a + (d / jnp.maximum(wsum, 1e-9)).astype(a.dtype), a),
                st["params"], dsum)
            version = version + has.astype(jnp.int32)
            hist = jax.tree.map(lambda h, a: h.at[version % H].set(a), hist,
                                params)
            gaps = jnp.zeros((B,), jnp.float32)
            if lat.get("avail_gap", 0) > 0:
                gaps = lat["avail_gap"] * jax.random.exponential(
                    jax.random.fold_in(k_sel, 103), (B,), jnp.float32)
            st = {
                **st, "params": params, "hist": hist, "t_done": t_done,
                "disp_ver": disp_ver, "dropped": dropped,
                "next_avail": st["next_avail"].at[out].set(new_clock + gaps,
                                                           mode="drop"),
                "last_done": st["last_done"].at[jnp.where(succ, idx, I32_MAX)]
                .set(t, mode="drop"),
                "ages": (st["ages"] + 1) * (1 - send.astype(jnp.int32)),
                "acc": _accumulate_gaps(st["acc"], send, r),
                "clock": new_clock, "version": version,
            }
            ys = {"loss": jnp.where(has, lsum / jnp.maximum(wsum, 1e-9), jnp.nan),
                  "clock": new_clock, "version": version,
                  "fill": valid.sum()}
            return st, ys

        return jax.lax.scan(step, st, r0 + jnp.arange(length))

    def follow(self, steps: int, chunk: int) -> Dict:
        """Run ``steps`` steps in chunks of ``chunk``; the params after each
        chunk, the eval loss after the last, the per-step outputs and the
        final state, all on the host."""
        st = self.init_state()
        out: Dict[str, List] = {"params": []}
        ys_all = []
        for r0 in range(0, steps, chunk):
            st, ys = self._chunk(st, self.data, r0, chunk)
            ys_all.append(jax.device_get(ys))
            out["params"].append(jax.device_get(st["params"]))
        out["eval"] = [float(self._eval(st["params"], self.test))]
        for key in ys_all[0]:
            out[key] = np.concatenate([y[key] for y in ys_all])
        out["params0"] = jax.device_get(self.params0)
        out["final"] = jax.device_get(
            {k: v for k, v in st.items() if k not in ("params", "hist")})
        return out
