#!/usr/bin/env python3
"""Records the scoped device trace ``test_xplane.py`` reduces, on one chip.

    python bench/tests/record_scoped_trace.py <out.xplane.pb>

One jitted ``chunk`` whose work is all under two of the engine's scope
names: ``pop`` around the ``event_topk`` kernel, and ``local_train``
around a ``jax.grad`` under ``vmap`` with the scope inside the
differentiated function, so the backward ops carry the scope inside
transformation wrappers (``vmap(transpose(jvp(local_train)))``). Three
calls, each inside the run loop's host spans (``run_engine.chunk``,
``run_engine.dispatch``, ``run_engine.pull``), with a 2 ms host sleep
under ``run_engine.record`` after each, all inside ``bench.window``.
"""
import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2] / "src")]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    def loss(w, x):
        with jax.named_scope("local_train"):
            return jnp.tanh(x @ w).sum()

    @jax.jit
    def chunk(t, ws, xs):
        with jax.named_scope("pop"):
            vals, idx = ops.event_next_k(t, 10)
            first = vals.sum()
        grads = jax.vmap(jax.grad(loss))(ws, xs)
        return first, idx, grads

    t = jax.random.uniform(jax.random.PRNGKey(0), (65536,))
    ws = jnp.ones((8, 256, 256)) / 256
    xs = jax.random.normal(jax.random.PRNGKey(1), (8, 256, 256))
    jax.block_until_ready(chunk(t, ws, xs))
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation("bench.window"):
            for r in range(3):
                with jax.profiler.StepTraceAnnotation("run_engine.chunk",
                                                      step_num=r):
                    with jax.profiler.TraceAnnotation("run_engine.dispatch"):
                        got = chunk(t, ws, xs)
                    with jax.profiler.TraceAnnotation("run_engine.pull"):
                        jax.device_get(got)
                    with jax.profiler.TraceAnnotation("run_engine.record"):
                        time.sleep(0.002)
        jax.profiler.stop_trace()
        shutil.copy(glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0], out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
