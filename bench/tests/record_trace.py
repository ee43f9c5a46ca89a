#!/usr/bin/env python3
"""Records the small device trace ``test_trace.py`` reduces, on one chip.

    python bench/tests/record_trace.py <out.xplane.pb>

Two jitted programs named as the engine's are (``chunk``, ``eval_scan``),
the ``event_topk`` kernel inside ``chunk``, host spans named as the
harness's, and an idle gap under ``engine.evaluate`` (a host sleep).
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

    @jax.jit
    def chunk(t, x):
        vals, idx = ops.event_next_k(t, 10)
        return vals.sum() + (x @ x).sum(), idx

    @jax.jit
    def eval_scan(x):
        return jnp.tanh(x @ x).sum()

    t = jax.random.uniform(jax.random.PRNGKey(0), (65536,))
    x = jnp.ones((512, 512))
    jax.block_until_ready((chunk(t, x), eval_scan(x)))
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("engine.run_chunk"):
                    jax.block_until_ready(chunk(t, x))
                with jax.profiler.TraceAnnotation("engine.evaluate"):
                    time.sleep(0.002)
                    jax.block_until_ready(eval_scan(x))
        jax.profiler.stop_trace()
        shutil.copy(glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0], out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
