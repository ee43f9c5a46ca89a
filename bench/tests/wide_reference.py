#!/usr/bin/env python3
"""Follows the plain reference over one sync chunk with the two-layer MLP
widened to a language model's parameter count, on one chip, and prints one
JSON line: its seconds, the process's peak device memory, and what the
compiler says of the same chunk with the whole cohort in one block.

    python bench/tests/wide_reference.py

The run is sync-paper's (n=100, k=15, m=10, a cohort of width 30, 600
examples a client in batches of 50, E=5: 60 local SGD steps a client) with
``toy_mlp.py``'s hidden layer 680,000 units wide: 540 M float32
parameters, 2.16 GB, about the 535 M of DeepSeek-V2-Lite cut to its dense
layer and 4 MoE layers of 8 experts over an eighth of the vocabulary. The
reference trains one client at a time at that size (``client_block``); the
chunk is followed twice, and the first compiles. A second reference, its
block budget raised until the cohort is one block, then follows the chunk
once: the compiler refuses it.
"""
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE)]
from bench import run as bench_run  # noqa: E402
import toy_mlp  # noqa: E402  (bench/tests/toy_mlp.py)

HIDDEN = 680_000
SEED = 7


def main() -> int:
    import jax

    from bench import reference
    from bench.cell import load_cell

    sys.modules["bench.models.toy_mlp"] = toy_mlp
    cell = load_cell("sync-paper")
    used = bench_run.check_chips(cell)
    bench_run.use_cache()
    config = {**cell.config, "model": "toy_mlp",
              "widths": {"image_size": 28, "channels": 1, "num_classes": 10,
                         "hidden": HIDDEN}}
    run_seed, data_seed = bench_run.seeds(SEED)
    data = toy_mlp.make_data(jax.random.PRNGKey(data_seed), config, 1)
    chunk = int(cell.traffic["run"]["steps_per_chunk"])

    def follow():
        ref = reference.Reference(toy_mlp, config, cell.traffic, data,
                                  run_seed)
        t0 = time.perf_counter()
        out = ref.follow(chunk, chunk)
        return ref, out, time.perf_counter() - t0

    ref, out, first = follow()
    _, out, second = follow()
    params = sum(a.size for a in jax.tree.leaves(ref.params0))
    line = {"hidden": HIDDEN, "params": int(params),
            "param_bytes": int(4 * params), "block": ref.client_block,
            "selected": [int(s) for s in out["sel"].sum(1)],
            "loss": [float(x) for x in out["loss"]],
            "eval": out["eval"], "first_s": first, "second_s": second,
            "peak_bytes": bench_run.peak_bytes(used),
            "device": used[0].device_kind}
    del ref, out
    gc.collect()
    reference.BLOCK_BYTES = 2**62  # the cohort's 30 clients in one block
    try:
        whole = follow()[0].client_block
        line["whole_cohort"] = {"block": whole, "ran": True}
    except Exception as e:  # the compiler's refusal says what it needs
        line["whole_cohort"] = {"refused": str(e).splitlines()[:3]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
