"""Async fleet simulator benchmarks: (a) the engine's step-driving hot
loop (admission -> dispatch -> pop -> re-arm, full event state, no
training) measured two ways — the legacy per-step pattern (one host
dispatch per step, non-donated state, one (n,) selection pull per step,
exactly what ``run_engine`` did before chunking) against the chunked
``ChunkRunner`` path (donated ``lax.scan``, device-resident load
accumulators, one transfer per chunk, counter-based RNG) — (b) sync
vs async federated training compared on *simulated* time-to-target
accuracy under a straggler-heavy profile — and (c) ``run_sharded``: the
mesh-sharded fleet state (per-device footprint + the O(devices * B) pop)
against the single-device chunked path, on fake CPU devices.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import load_metric as lm
from repro.core.aoi import age_update
from repro.engine.chunk import ChunkRunner, dealias_pytree, run_key
from repro.sim import events as ev_mod
from repro.sim import latency as lat_mod

KEY = jax.random.PRNGKey(0)

# chunked-path parameters: steps per donated scan dispatch, and the
# counter-based generator used for the fleet-scale fast path
CHUNK = 64
FAST_RNG = "unsafe_rbg"


def _make_sim_step(probs, m, profile, buffer_size, use_kernel, n=None, mesh=None):
    """One engine sim step over the *full* event state (the async
    engine's bookkeeping minus local training): markov admission ->
    dispatch with sampled latency/dropout -> pop next-k completions ->
    clock advance -> availability re-arm. ``step(state, key)`` with
    state = {sched, ev, speed, clock}.

    With ``mesh`` (a 1-D fleet mesh; ``n`` required, divisible by the
    mesh), the per-client state is sharded exactly like the
    ``ShardedAsyncEngine`` carry and the pop runs through the
    O(devices * B) ``sharded_next_k_events`` merge."""
    if mesh is None:
        def pop(ev):
            return ev_mod.pop_events(ev, buffer_size, use_kernel=use_kernel)

        def constrain(state):
            return state
    else:
        from repro.core import distributed as dist
        from repro.engine.sharded import fleet_state_sharding

        axis = mesh.axis_names[0]
        next_k = dist.sharded_next_k_events(mesh, n, buffer_size, axis=axis)

        def pop(ev):
            t, idx = next_k(ev["t_done"])
            return ev_mod.apply_pop(ev, t, idx)

        def constrain(state):
            return jax.tree.map(
                jax.lax.with_sharding_constraint,
                state,
                fleet_state_sharding(mesh, n, state, axis),
            )

    def step(state, key, data=None):  # no client data: nothing trains
        ev, ages, clock = state["ev"], state["sched"], state["clock"]
        k_sel, k_lat = jax.random.split(key)
        k_gap = jax.random.fold_in(k_sel, 103)

        idle = jnp.isinf(ev["t_done"])
        available = ev["next_avail"] <= clock
        send_p = probs[jnp.minimum(ages, m)]
        want = jax.random.uniform(k_sel, ages.shape) < send_p
        send = want & idle & available
        ages = age_update(ages, send)

        latency = lat_mod.sample_latency(k_lat, profile, state["speed"])
        # zero-dropout profiles skip the 102 fold (the engine does too;
        # sample_dropout already skips the (n,) draw itself)
        if profile.dropout > 0:
            dropped = lat_mod.sample_dropout(
                jax.random.fold_in(k_sel, 102), profile, ages.shape[0]
            )
        else:
            dropped = jnp.zeros((ages.shape[0],), jnp.bool_)
        ev = ev_mod.schedule_completions(
            ev, send, clock, latency, jnp.zeros((), jnp.int32), dropped
        )
        t_ev, idx, valid, ev = pop(ev)
        clock = jnp.maximum(clock, jnp.max(jnp.where(valid, t_ev, -jnp.inf)))
        clock = jnp.where(
            valid.any(), clock, jnp.maximum(clock, jnp.min(ev["next_avail"]))
        )
        gaps = lat_mod.sample_avail_gap(k_gap, profile, buffer_size)
        ev = {
            **ev,
            "next_avail": ev["next_avail"]
            .at[ev_mod.scatter_idx(idx, valid)]
            .set(clock + gaps, mode="drop"),
            "last_done": ev["last_done"]
            .at[ev_mod.scatter_idx(idx, valid)]
            .set(t_ev, mode="drop"),
        }
        state = constrain({**state, "ev": ev, "sched": ages, "clock": clock})
        return state, {"send": send, "clock": clock}

    return step


def _sim_state(n, profile, key):
    return {
        "sched": jnp.zeros((n,), jnp.int32),
        "ev": ev_mod.init_event_state(n),
        "speed": lat_mod.client_speed(key, n, profile),
        "clock": jnp.zeros((), jnp.float32),
    }


def _bench_pure_engine(csv_rows, n, m, profile, trials=5):
    k = max(int(n * 0.15), 1)
    buf = min(max(n // 100, 16), 4096)
    probs = jnp.asarray(lm.optimal_probs(n, k, m), jnp.float32)
    on_cpu = jax.default_backend() == "cpu"
    # Pallas kernel path runs interpreted on CPU (too slow to time);
    # benchmark the jnp reference there, the kernel on real backends
    step_fn = _make_sim_step(probs, m, profile, buf, use_kernel=not on_cpu)

    # --- legacy hot loop: per-step dispatch + per-step (n,) host pull
    perstep = jax.jit(step_fn)

    # --- chunked hot loop: donated scan + device stats, one pull/chunk
    runner = ChunkRunner(step_fn, aux_keys=("clock",))

    # both paths must time the *same simulation regime*: the step's cost
    # is phase-dependent (top-k over a saturating in-flight set), so warm
    # the fleet towards steady state once and restart every timed trial
    # from copies of that snapshot
    snap = {
        **_sim_state(n, profile, KEY),
        "k_run": run_key(0, FAST_RNG),
        "load_acc": lm.init_selection_accum(n, k),
    }
    snap, _ = runner(dealias_pytree(snap), 0, CHUNK, with_history=False)
    snap, _ = runner(snap, CHUNK, CHUNK, with_history=False)
    jax.block_until_ready(snap["clock"])
    r0 = 2 * CHUNK

    def sim_only(st):
        return {k: v for k, v in st.items() if k not in ("k_run", "load_acc")}

    state_p = sim_only(snap)
    perstep(state_p, KEY)  # compile

    def time_perstep(iters):
        state = sim_only(snap)
        t0 = time.time()
        for i in range(iters):
            state, aux = perstep(state, jax.random.fold_in(KEY, r0 + i))
            _ = np.asarray(aux["send"])  # the old per-step history pull
        jax.block_until_ready(state["clock"])
        return (time.time() - t0) / iters * 1e6

    def time_chunked():
        state = jax.tree.map(jnp.copy, snap)  # donated below; keep snap
        t0 = time.time()
        state, aux = runner(state, r0, CHUNK, with_history=False)
        _ = jax.device_get(aux)  # one transfer per chunk
        return (time.time() - t0) / CHUNK * 1e6

    # interleaved trials + medians: shared boxes drift ~2x in throughput
    # over seconds, so the two paths must also sample the same machine
    # conditions for the ratio to mean anything
    iters = max(4, min(16, 2_000_000 // n))
    per_us, ch_us = [], []
    for _ in range(trials):
        per_us.append(time_perstep(iters))
        ch_us.append(time_chunked())
    per, ch = float(np.median(per_us)), float(np.median(ch_us))
    speedup = per / ch
    path = "jnp" if on_cpu else "kernel"
    print(f"  n={n:>9,} buffer={buf:5d} perstep {per / 1e3:8.2f} ms/step | "
          f"chunked {ch / 1e3:8.2f} ms/step  ({speedup:4.2f}x, {path})")
    csv_rows.append((f"async_engine_step_n{n}_perstep", per,
                     f"buffer={buf};path=perstep+pull;rng=threefry"))
    csv_rows.append((f"async_engine_step_n{n}", ch,
                     f"buffer={buf};path=chunked{CHUNK};rng={FAST_RNG};"
                     f"kernel={path};speedup={speedup:.2f}x"))


def _bench_var_x_workload(csv_rows, n, m, profile, steps):
    """The paper's telemetry workload, end to end: drive the engine for
    ``steps`` server steps *and produce the load statistics* (Var[X],
    cohort moments). The pre-chunking engine could only do this by
    materializing the (steps, n) selection history — one (n,) host pull
    per step plus an O(n)-per-client host gap extraction at finalize —
    while the chunked engine folds O(1)-per-step sufficient statistics
    into the scan and finalizes from scalars."""
    k = max(int(n * 0.15), 1)
    buf = min(max(n // 100, 16), 4096)
    probs = jnp.asarray(lm.optimal_probs(n, k, m), jnp.float32)
    on_cpu = jax.default_backend() == "cpu"
    step_fn = _make_sim_step(probs, m, profile, buf, use_kernel=not on_cpu)

    # legacy: per-step dispatch, history matrix, numpy finalize
    perstep = jax.jit(step_fn)
    state = _sim_state(n, profile, KEY)
    state, _ = perstep(state, KEY)  # compile
    jax.block_until_ready(state["clock"])
    hist = np.zeros((steps, n), dtype=bool)
    t0 = time.time()
    for r in range(steps):
        state, aux = perstep(state, jax.random.fold_in(KEY, r))
        hist[r] = np.asarray(aux["send"])
    stats_old = lm.empirical_load_stats(hist)
    per = (time.time() - t0) / steps * 1e6

    # chunked: donated scan, device accumulators, scalar finalize
    runner = ChunkRunner(step_fn, aux_keys=("clock",))
    state = dealias_pytree({
        **_sim_state(n, profile, KEY),
        "k_run": run_key(0, FAST_RNG),
        "load_acc": lm.init_selection_accum(n, k),
    })
    state, _ = runner(state, 0, steps, with_history=False)  # compile
    state = dealias_pytree({
        **_sim_state(n, profile, jax.random.fold_in(KEY, 1)),
        "k_run": run_key(1, FAST_RNG),
        "load_acc": lm.init_selection_accum(n, k),
    })
    jax.block_until_ready(state["clock"])
    t0 = time.time()
    state, aux = runner(state, 0, steps, with_history=False)
    _ = jax.device_get(aux)
    stats_new = lm.selection_stats_from_accum(state["load_acc"])
    ch = (time.time() - t0) / steps * 1e6

    speedup = per / ch
    print(f"  n={n:>9,} {steps:3d} steps: history+numpy {per / 1e3:8.2f} ms/step"
          f" | accumulators {ch / 1e3:8.2f} ms/step  ({speedup:5.1f}x)  "
          f"[Var[X] {stats_old['var_X']:.1f} vs {stats_new['var_X']:.1f}]")
    csv_rows.append((f"async_var_x_workload_n{n}", ch,
                     f"steps={steps};legacy_us={per:.1f};speedup={speedup:.2f}x"))


def _state_bytes(state) -> int:
    def nbytes(arr):
        try:
            return arr.nbytes
        except (NotImplementedError, AttributeError):
            return 0  # typed PRNG key arrays hide their buffer; negligible

    return sum(nbytes(leaf) for leaf in jax.tree.leaves(state))


def run_sharded(csv_rows, trials: int = 3):
    """ShardedAsyncEngine's hot loop vs the single-device chunked path:
    the same sim step with the fleet state sharded over every local
    device and the buffer pop routed through the O(devices * B)
    local-top-B + all_gather + merge.

    On fake CPU devices (XLA_FLAGS=--xla_force_host_platform_device_count=8,
    the recipe CI uses) all shards share one physical CPU, so wall time
    measures overhead, not the win — the decisive columns are the
    *per-device* footprint (state bytes on one device, compiled
    argument/temp sizes) and the O(devices * B) pop communication, which
    is what lets the fleet outgrow a single accelerator's memory.
    """
    from repro.core import distributed as dist
    from repro.engine.sharded import fleet_state_sharding, per_device_state_bytes

    n_devs = jax.local_device_count()
    print("\n== sharded fleet state: per-device footprint + chunked step ==")
    if n_devs < 2:
        print("  [single device: set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8 for the "
              "sharded-vs-single-device comparison; skipping]")
        return
    m = 10
    profile = lat_mod.get_profile("lognormal")

    def build(n, mesh):
        k = max(int(n * 0.15), 1)
        buf = min(max(n // 100, 16), 4096)
        probs = jnp.asarray(lm.optimal_probs(n, k, m), jnp.float32)
        step_fn = _make_sim_step(probs, m, profile, buf, use_kernel=False,
                                 n=n, mesh=mesh)
        # dealias *before* device_put: putting the same constant-cache
        # buffer twice in one call can hand two leaves one buffer, which
        # the donated chunk then (fatally) donates twice
        state = dealias_pytree({
            **_sim_state(n, profile, KEY),
            "k_run": run_key(0, FAST_RNG),
            "load_acc": lm.init_selection_accum(n, k),
        })
        if mesh is not None:
            state = jax.device_put(
                state, fleet_state_sharding(mesh, n, state, mesh.axis_names[0])
            )
        return step_fn, state, buf

    def time_chunked(runner, snap):
        # warm towards steady state + compile, then timed trials from
        # copies of the snapshot (same regime for both paths)
        snap, _ = runner(snap, 0, CHUNK, with_history=False)
        snap, _ = runner(snap, CHUNK, CHUNK, with_history=False)
        jax.block_until_ready(snap["clock"])
        out = []
        for _ in range(trials):
            state = jax.tree.map(jnp.copy, snap)
            t0 = time.time()
            state, aux = runner(state, 2 * CHUNK, CHUNK, with_history=False)
            _ = jax.device_get(aux)
            out.append((time.time() - t0) / CHUNK * 1e6)
        return float(np.median(out)), snap

    def mem_line(step_fn, state):
        sim = {k: v for k, v in state.items() if k not in ("k_run", "load_acc")}
        stats = jax.jit(step_fn).lower(sim, KEY).compile().memory_analysis()
        return int(stats.argument_size_in_bytes), int(stats.temp_size_in_bytes)

    # --- timed comparison: one fleet size, sharded vs single device
    n = 262_144
    D = dist.resolve_fleet_shards(n, 0, n_devs)
    mesh = dist.fleet_mesh(D)
    dev0 = mesh.devices.flat[0]
    single_fn, single_state, buf = build(n, None)
    shard_fn, shard_state, _ = build(n, mesh)
    single_us, single_state = time_chunked(
        ChunkRunner(single_fn, aux_keys=("clock",)), single_state)
    shard_us, shard_state = time_chunked(
        ChunkRunner(shard_fn, aux_keys=("clock",)), shard_state)
    full_b = _state_bytes(single_state)
    per_dev_b = per_device_state_bytes(shard_state, dev0)
    s_arg, s_tmp = mem_line(shard_fn, shard_state)
    u_arg, u_tmp = mem_line(single_fn, single_state)
    print(f"  n={n:>9,} buffer={buf}: single {single_us / 1e3:8.2f} ms/step "
          f"state {full_b / 1e6:7.1f} MB | sharded x{D} "
          f"{shard_us / 1e3:8.2f} ms/step state/dev {per_dev_b / 1e6:7.1f} MB "
          f"(args {s_arg / 1e6:.1f} vs {u_arg / 1e6:.1f} MB, "
          f"temps {s_tmp / 1e6:.1f} vs {u_tmp / 1e6:.1f} MB)")
    csv_rows.append((
        f"async_engine_step_n{n}_sharded{D}", shard_us,
        f"buffer={buf};singledev_us={single_us:.1f};"
        f"state_per_dev_B={per_dev_b};state_full_B={full_b};"
        f"arg_B={s_arg};arg_full_B={u_arg};temp_B={s_tmp};temp_full_B={u_tmp}",
    ))

    # --- fleet size past a single accelerator's budget: sharded only
    n = 4_194_304
    D = dist.resolve_fleet_shards(n, 0, n_devs)
    mesh = dist.fleet_mesh(D)
    shard_fn, shard_state, buf = build(n, mesh)
    runner = ChunkRunner(shard_fn, aux_keys=("clock",))
    shard_state, _ = runner(shard_state, 0, 8, with_history=False)  # compile
    jax.block_until_ready(shard_state["clock"])
    t0 = time.time()
    shard_state, aux = runner(shard_state, 8, 8, with_history=False)
    _ = jax.device_get(aux)
    us = (time.time() - t0) / 8 * 1e6
    full_b = _state_bytes(shard_state)
    per_dev_b = per_device_state_bytes(shard_state, mesh.devices.flat[0])
    print(f"  n={n:>9,} buffer={buf}: sharded x{D} {us / 1e3:8.2f} ms/step | "
          f"state/dev {per_dev_b / 1e6:7.1f} MB of {full_b / 1e6:7.1f} MB total "
          f"({full_b / per_dev_b:.1f}x below the single-device footprint)")
    csv_rows.append((
        f"async_fleet_state_n{n}_sharded{D}", us,
        f"buffer={buf};state_per_dev_B={per_dev_b};state_full_B={full_b}",
    ))


def _mlp_task(n, n_eval=4096, d=16, hidden=128, classes=10, examples=2,
              seed=0):
    """A real FLTask at fleet scale whose cohort training is the step's
    dominant cost: tiny per-client shards (so a 262k-client fleet's data
    fits in memory) feeding an MLP big enough that the vmapped cohort of
    local updates dwarfs the event bookkeeping — the workload
    cohort-parallel execution is for."""
    from repro.fl.task import FLTask

    kd, ke, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    teacher = jax.random.normal(kw, (d, classes), jnp.float32)

    def draw(key, count):
        x = jax.random.normal(key, (count, d), jnp.float32)
        return x, jnp.argmax(x @ teacher, axis=-1)

    x, y = draw(kd, n * examples)
    cx, cy = x.reshape(n, examples, d), y.reshape(n, examples)
    tx, ty = draw(ke, n_eval)

    def init(key):
        k1, k2 = jax.random.split(key)
        return {
            "w1": (2.0 / d) ** 0.5
            * jax.random.normal(k1, (d, hidden), jnp.float32),
            "b1": jnp.zeros((hidden,), jnp.float32),
            "w2": (2.0 / hidden) ** 0.5
            * jax.random.normal(k2, (hidden, classes), jnp.float32),
            "b2": jnp.zeros((classes,), jnp.float32),
        }

    def logits_fn(p, xb):
        return jax.nn.relu(xb @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]

    def loss_fn(p, batch):
        logp = jax.nn.log_softmax(logits_fn(p, batch["x"]))
        return -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1).mean()

    def eval_batch_fn(p, data):
        logits = logits_fn(p, data["x"])
        logp = jax.nn.log_softmax(logits)
        cnt = data["y"].shape[0]
        return {
            "loss": -jnp.take_along_axis(
                logp, data["y"][:, None], axis=-1
            ).sum() / cnt,
            "accuracy": (logits.argmax(-1) == data["y"]).sum() / cnt,
        }

    eval_data = {"x": tx, "y": ty}
    return FLTask(
        name=f"bench-mlp-n{n}", init=init, loss_fn=loss_fn,
        eval_fn=jax.jit(lambda p: eval_batch_fn(p, eval_data)),
        client_data={"x": cx, "y": cy}, examples_per_client=examples,
        eval_data=eval_data, eval_batch_fn=eval_batch_fn,
    )


def _time_engine_chunks(engines, chunk, trials):
    """Per-step medians for several engines driving the same workload,
    trials interleaved so every engine samples the same machine
    conditions (shared boxes drift)."""
    snaps = []
    for eng in engines:
        state = eng.init()
        state, _ = eng.run_chunk(state, 0, chunk, False)  # compile + warm
        state, _ = eng.run_chunk(state, chunk, chunk, False)
        jax.block_until_ready(jax.tree.leaves(state["params"])[0])
        snaps.append(state)
    times = [[] for _ in engines]
    for _ in range(trials):
        for i, eng in enumerate(engines):
            st = jax.tree.map(jnp.copy, snaps[i])  # run_chunk donates
            t0 = time.time()
            st, aux = eng.run_chunk(st, 2 * chunk, chunk, False)
            _ = jax.device_get(aux)
            times[i].append((time.time() - t0) / chunk * 1e6)
    return [float(np.median(t)) for t in times], snaps


def run_cohort(csv_rows, trials: int = 3):
    """Cohort-parallel execution (RunConfig.shard_cohort) vs the
    replicated-cohort layout, on the *real* engines with training in the
    step: flag-off pins every (B,)/(width,) intermediate replicated, so
    all devices redundantly run the full cohort vmap; flag-on partitions
    it, so each device trains cohort/devices clients and the aggregators
    merge with one psum of the accumulator pytree. Unlike the sim-only
    rows above, these rows measure what sharded fleets actually pay per
    step when the cohort work dominates — the case the flag exists for."""
    import dataclasses as dc

    from repro.core import distributed as dist
    from repro.engine import (
        AsyncEngine,
        RunConfig,
        ShardedAsyncEngine,
        SyncEngine,
        make_engine,
    )

    n_devs = jax.local_device_count()
    print("\n== cohort-parallel engine step: sharded vs replicated cohort ==")
    if n_devs < 2:
        print("  [single device: set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8 for the "
              "cohort-sharded comparison; skipping]")
        return
    chunk = 8

    # --- async: 262k-client fleet, 2621-wide buffer (matches the sim-only
    # sharded row's shape), MLP cohort training in the step
    n = 262_144
    k = max(int(n * 0.15), 1)
    buf = min(max(n // 100, 16), 4096)
    D = dist.resolve_fleet_shards(n, 0, n_devs)
    task = _mlp_task(n)
    base = RunConfig(
        n_clients=n, k=k, m=10, policy="markov", rounds=4 * chunk,
        local_epochs=1, batch_size=2, mode="async", buffer_size=buf,
        profile="lognormal", steps_per_chunk=chunk, collect_history=False,
        rng_impl=FAST_RNG, eval_every=4 * chunk,
    )
    single = AsyncEngine(task, base)
    repl = ShardedAsyncEngine(task, dc.replace(base, mesh_shards=0))
    coh = make_engine(task, dc.replace(
        base, mesh_shards=0, shard_cohort=True
    ))
    (single_us, repl_us, coh_us), snaps = _time_engine_chunks(
        [single, repl, coh], chunk, trials
    )
    repl_dev_b = repl.per_device_state_bytes(snaps[1])
    coh_dev_b = coh.per_device_state_bytes(snaps[2])
    print(f"  async n={n:>9,} buffer={buf}: single {single_us / 1e3:8.2f} "
          f"ms/step | replicated x{D} {repl_us / 1e3:8.2f} ms/step | "
          f"cohort-sharded x{D} {coh_us / 1e3:8.2f} ms/step "
          f"({repl_us / coh_us:.2f}x vs replicated; state/dev "
          f"{coh_dev_b / 1e6:.1f} vs {repl_dev_b / 1e6:.1f} MB)")
    csv_rows.append((
        f"async_engine_step_n{n}_sharded{D}_cohort", coh_us,
        f"buffer={buf};replicated_us={repl_us:.1f};"
        f"singledev_us={single_us:.1f};"
        f"speedup_vs_replicated={repl_us / coh_us:.2f}x;"
        f"state_per_dev_B={coh_dev_b};state_per_dev_replicated_B={repl_dev_b}",
    ))

    # --- sync: same fleet, k sized so the padded cohort vmap is the round
    sk = 2048
    sbase = RunConfig(
        n_clients=n, k=sk, m=10, policy="markov", rounds=4 * chunk,
        local_epochs=1, batch_size=2, mode="sync",
        steps_per_chunk=chunk, collect_history=False, rng_impl=FAST_RNG,
        eval_every=4 * chunk,
    )
    width = sbase.cohort_width()
    ssingle = SyncEngine(task, sbase)
    scoh = make_engine(task, dc.replace(
        sbase, mesh_shards=0, shard_cohort=True
    ))
    (ssingle_us, scoh_us), _ = _time_engine_chunks(
        [ssingle, scoh], chunk, trials
    )
    print(f"  sync  n={n:>9,} width={width}: single {ssingle_us / 1e3:8.2f} "
          f"ms/round | cohort-sharded x{scoh.mesh_shards} "
          f"{scoh_us / 1e3:8.2f} ms/round "
          f"({ssingle_us / scoh_us:.2f}x vs single device)")
    csv_rows.append((
        f"sync_engine_round_n{n}_cohort{scoh.mesh_shards}", scoh_us,
        f"width={width};singledev_us={ssingle_us:.1f};"
        f"speedup_vs_single={ssingle_us / scoh_us:.2f}x",
    ))


def run_topo(csv_rows, trials: int = 3):
    """Topology-aware aggregation (``repro.topo``): the 2-tier
    hierarchical reduction (edge -> regional -> global) on the real
    async engine vs the flat star, single device and with the fleet
    state sharded over every local device. The tiered path segment-sums
    per-node aggregator accumulators up the tree and still merges
    cross-device with the one-psum pattern, so the decisive check is
    that the hierarchy's cost is a small constant over the star — the
    per-tier Var[X] telemetry and per-hop latency ride along in the
    same donated scan."""
    import dataclasses as dc

    from repro.core import distributed as dist
    from repro.engine import AsyncEngine, RunConfig, make_engine

    n_devs = jax.local_device_count()
    print("\n== hierarchical aggregation topology: 2-tier vs star ==")
    if n_devs < 2:
        print("  [single device: set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8 for the "
              "sharded topology comparison; skipping]")
        return
    chunk = 8
    n = 262_144
    k = max(int(n * 0.15), 1)
    buf = min(max(n // 100, 16), 4096)
    D = dist.resolve_fleet_shards(n, 0, n_devs)
    tiers = (64, 8)
    task = _mlp_task(n)
    base = RunConfig(
        n_clients=n, k=k, m=10, policy="markov", rounds=4 * chunk,
        local_epochs=1, batch_size=2, mode="async", buffer_size=buf,
        profile="lognormal", steps_per_chunk=chunk, collect_history=False,
        rng_impl=FAST_RNG, eval_every=4 * chunk,
    )
    hcfg = dc.replace(base, topology="hierarchical",
                      topology_kwargs={"tiers": tiers})
    star = AsyncEngine(task, base)
    hier = AsyncEngine(task, hcfg)
    shard = make_engine(task, dc.replace(hcfg, mesh_shards=0))
    (star_us, hier_us, shard_us), snaps = _time_engine_chunks(
        [star, hier, shard], chunk, trials
    )
    # the per-tier load telemetry must have accumulated device-resident
    tier_stats = lm.tier_stats_from_accum(snaps[1]["tier_acc"])
    nodes = len(tier_stats["tier_var_X"])
    samples = int(sum(tier_stats["tier_num_samples"]))
    tag = "x".join(str(t) for t in tiers)
    print(f"  async n={n:>9,} buffer={buf} tiers={tiers}: star "
          f"{star_us / 1e3:8.2f} ms/step | hier {hier_us / 1e3:8.2f} ms/step "
          f"({hier_us / star_us:.2f}x) | hier sharded x{D} "
          f"{shard_us / 1e3:8.2f} ms/step "
          f"[{nodes} tier-0 nodes, {samples:,} gap samples]")
    csv_rows.append((
        f"async_engine_step_n{n}_hier{tag}", hier_us,
        f"buffer={buf};tiers={tag};star_us={star_us:.1f};"
        f"overhead_vs_star={hier_us / star_us:.2f}x;"
        f"tier0_nodes={nodes};tier_gap_samples={samples}",
    ))
    csv_rows.append((
        f"async_engine_step_n{n}_hier{tag}_sharded{D}", shard_us,
        f"buffer={buf};tiers={tag};singledev_us={hier_us:.1f};"
        f"star_us={star_us:.1f}",
    ))


def run(csv_rows, rounds: int = 12):
    print("\n== async engine hot loop: per-step+pull vs chunked scan ==")
    m = 10
    profile = lat_mod.get_profile("lognormal")
    for n in (10_000, 100_000, 1_000_000):
        _bench_pure_engine(csv_rows, n, m, profile)

    print("\n== Var[X] telemetry workload: history+numpy vs device accums ==")
    for n, steps in ((100_000, 64), (1_000_000, 16)):
        _bench_var_x_workload(csv_rows, n, m, profile, steps)

    print("\n== sync vs async: simulated time-to-target accuracy ==")
    from repro.configs.paper_cnn import MNIST_CNN
    from repro.data.synthetic import make_image_dataset
    from repro.engine import RunConfig, make_engine, run_engine
    from repro.fl import make_cnn_task

    small = dataclasses.replace(
        MNIST_CNN, name="paper-cnn-mnist-bench", image_size=16,
        conv_channels=(8, 16), fc_width=64,
    )
    train, test = make_image_dataset("mnist-bench", 10, 16, 1, 1200, 500, seed=0,
                                     difficulty=0.8)
    task = make_cnn_task(small, train, test, n_clients=40)
    base = RunConfig(n_clients=40, k=8, m=8, policy="markov", rounds=rounds,
                     local_epochs=2, batch_size=10, eval_every=1)
    profile_name = "lognormal"
    mean_lat = lat_mod.get_profile(profile_name).mean_latency()

    t0 = time.time()
    sync = run_engine(make_engine(task, base))
    sync_s = time.time() - t0
    sim_sync_t = lat_mod.simulate_sync_duration(
        sync.selection, lat_mod.get_profile(profile_name),
        jax.random.fold_in(KEY, 7),
    )

    t0 = time.time()
    acfg = dataclasses.replace(base, mode="async", buffer_size=base.k,
                               profile=profile_name)
    asy = run_engine(make_engine(task, acfg))
    async_s = time.time() - t0

    acc_sync = sync.records[-1].accuracy
    acc_async = asy.records[-1].accuracy
    sim_async_t = asy.wall_stats["sim_time"]
    print(f"  sync : acc={acc_sync:.3f} simulated {sim_sync_t:8.1f}s "
          f"(slowest-client rounds, mean client latency {mean_lat:.2f}s)")
    print(f"  async: acc={acc_async:.3f} simulated {sim_async_t:8.1f}s "
          f"(staleness mean {asy.wall_stats['mean_staleness']:.2f})")
    csv_rows.append(("async_vs_sync_sim_time", sim_async_t * 1e6,
                     f"sync={sim_sync_t:.1f}s;acc_async={acc_async:.3f};"
                     f"acc_sync={acc_sync:.3f}"))
    csv_rows.append(("async_train_steps", async_s / max(rounds, 1) * 1e6,
                     f"host_s={async_s:.1f};sync_host_s={sync_s:.1f}"))
