"""Plain float32 reference of the federated runs the benchmark times.

It imports nothing of the program and takes nothing the program made: it
draws its own parameters, selections, latencies and local batches from
the run's seed with the same ``jax.random`` calls the semantics name, and
computes every matrix product at ``Precision.HIGHEST``. Written for
reading, not speed: straightforward ``jax.numpy``, one client at a time
inside a block (``vmap`` over a block of clients, ``scan`` over blocks,
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

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# clients trained together inside one block of the cohort
CLIENT_BLOCK = 125
I32_MAX = np.iinfo(np.int32).max


# ---------------------------------------------------------------- model


def cnn_init(key, w: dict, dtype) -> Dict:
    """He-normal weights, zero biases (McMahan et al.'s CNN)."""
    ks = jax.random.split(key, 4)
    c1, c2 = w["conv_channels"]
    kk, s = w["kernel"], w["image_size"] // 4
    flat = s * s * c2

    def he(k, shape, fan_in):
        return jax.random.normal(k, shape) * (2.0 / fan_in) ** 0.5

    p = {
        "conv1": {"w": he(ks[0], (kk, kk, w["channels"], c1),
                          kk * kk * w["channels"]), "b": jnp.zeros((c1,))},
        "conv2": {"w": he(ks[1], (kk, kk, c1, c2), kk * kk * c1),
                  "b": jnp.zeros((c2,))},
        "fc1": {"w": he(ks[2], (flat, w["fc_width"]), flat),
                "b": jnp.zeros((w["fc_width"],))},
        "fc2": {"w": he(ks[3], (w["fc_width"], w["num_classes"]),
                        w["fc_width"]), "b": jnp.zeros((w["num_classes"],))},
    }
    return jax.tree.map(lambda a: a.astype(dtype), p)


def cnn_forward(p, x):
    def conv(x, q):
        y = jax.lax.conv_general_dilated(
            x, q["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
        return y + q["b"]

    def pool(x):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                     (1, 2, 2, 1), "VALID")

    x = pool(jax.nn.relu(conv(x, p["conv1"])))
    x = pool(jax.nn.relu(conv(x, p["conv2"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, p["fc1"]["w"], precision=HIGHEST)
                    + p["fc1"]["b"])
    return jnp.dot(x, p["fc2"]["w"], precision=HIGHEST) + p["fc2"]["b"]


def xent(p, x, y):
    logp = jax.nn.log_softmax(cnn_forward(p, x))
    return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()


def local_train(p, xs, ys, key, lr, epochs: int, batch: int):
    """``epochs`` passes of SGD over one client's examples."""
    examples = xs.shape[0]
    nb, bs = max(examples // batch, 1), min(batch, examples)
    perms = jax.vmap(
        lambda k: jax.random.permutation(k, examples)[:nb * bs].reshape(nb, bs)
    )(jax.random.split(key, epochs)).reshape(epochs * nb, bs)

    def sgd(p, idx):
        loss, g = jax.value_and_grad(xent)(p, xs[idx], ys[idx])
        return jax.tree.map(lambda a, b: (a - lr * b).astype(a.dtype), p, g), loss

    p, losses = jax.lax.scan(sgd, p, perms)
    return p, losses.mean()


def evaluate(p, tx, ty, batch: int = 500):
    """Mean loss over the test set in batches of ``batch`` (a last
    partial batch is left out)."""
    bs = min(batch, tx.shape[0])
    nb = max(tx.shape[0] // bs, 1)
    xb = tx[:nb * bs].reshape((nb, bs) + tx.shape[1:])
    yb = ty[:nb * bs].reshape(nb, bs)

    def one(carry, b):
        logp = jax.nn.log_softmax(cnn_forward(p, b[0]).astype(jnp.float32))
        return carry - jnp.take_along_axis(logp, b[1][:, None], axis=-1).sum(), None

    total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32), (xb, yb))
    return total / (nb * bs)


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


def _block_train(params_of, data, idx, keys, lrs, weights, epochs, batch):
    """Weighted sum over the cohort of (trained params - start params) and
    of the local losses, training ``CLIENT_BLOCK`` clients at a time.
    ``params_of(j)`` is slot ``j``'s start params."""
    width = idx.shape[0]
    blk = min(CLIENT_BLOCK, width)
    pad = -width % blk
    slots = jnp.arange(width + pad).reshape(-1, blk)

    def one(j):
        j = jnp.minimum(j, width - 1)
        start = params_of(j)
        got, loss = local_train(start, data["x"][idx[j]], data["y"][idx[j]],
                                keys[j], lrs[j], epochs, batch)
        return jax.tree.map(lambda a, b: a - b, got, start), loss

    def body(acc, js):
        deltas, losses = jax.vmap(one)(js)
        w = jnp.where(js < width, weights[jnp.minimum(js, width - 1)], 0.0)
        dsum = jax.tree.map(
            lambda s, d: s + jnp.tensordot(w.astype(d.dtype), d, axes=1,
                                           precision=HIGHEST), acc[0], deltas)
        return (dsum, acc[1] + (w * losses.astype(jnp.float32)).sum()), None

    zero = jax.tree.map(jnp.zeros_like, params_of(0))
    (dsum, lsum), _ = jax.lax.scan(body, (zero, jnp.zeros((), jnp.float32)),
                                   slots)
    return dsum, lsum


# ---------------------------------------------------------------- runs


class Reference:
    """One configuration's reference run from a seed.

    ``dtype`` is the type parameters, data and arithmetic are held in:
    float32 for the reference, bfloat16 for its control."""

    def __init__(self, config: dict, traffic: dict, data, seed: int,
                 dtype=jnp.float32):
        self.run = {**config["run"], **traffic["run"]}
        self.widths = config["widths"]
        self.latency = config.get("latency", {})
        self.dtype = dtype
        # arguments of the jitted programs: closed over, XLA would embed
        # the fleet's data as a constant
        self.data = {"x": data.x.astype(dtype), "y": data.y}
        self.test = (data.test_x.astype(dtype), data.test_y)
        run = self.run
        if run["policy"] != "markov":
            raise ValueError(f"the reference follows the markov policy only, "
                             f"not {run['policy']!r}")
        self.n, self.k, self.m = run["n_clients"], run["k"], run["m"]
        p = markov_probs(self.n, self.k, self.m)
        self.p = jnp.asarray(p)
        self.pi = jnp.asarray(stationary(p).astype(np.float32))
        k_init, k_policy, self.k_run = jax.random.split(
            jax.random.PRNGKey(seed), 3)
        self.params0 = cnn_init(k_init, self.widths, dtype)
        self.ages0 = jax.random.choice(k_policy, self.m + 1, shape=(self.n,),
                                       p=self.pi).astype(jnp.int32)
        self.sync = run.get("mode", "sync") == "sync"
        self._chunk = jax.jit(self._sync_chunk if self.sync
                              else self._async_chunk, static_argnums=3)
        self._eval = jax.jit(evaluate)

    def lr(self, t):
        return (jnp.asarray(self.run["lr0"], jnp.float32)
                * self.run["lr_decay"] ** t.astype(jnp.float32))

    def _want(self, k_sel, ages):
        return jax.random.uniform(k_sel, (self.n,)) < self.p[jnp.minimum(ages, self.m)]

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
        run = self.run
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
            dsum, lsum = _block_train(lambda j: g, data, idx, keys, lrs,
                                      mask, run["local_epochs"],
                                      run["batch_size"])
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
            dsum, lsum = _block_train(
                lambda j: jax.tree.map(lambda h: h[read[j]], hist), data,
                idx, keys, lrs, w, run["local_epochs"], run["batch_size"])
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
        out["eval"] = [float(self._eval(st["params"], *self.test))]
        for key in ys_all[0]:
            out[key] = np.concatenate([y[key] for y in ys_all])
        out["params0"] = jax.device_get(self.params0)
        out["final"] = jax.device_get(
            {k: v for k, v in st.items() if k not in ("params", "hist")})
        return out
