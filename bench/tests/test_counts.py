"""The counts kept with the benchmark: FLOPs of the CNN and the table of
peaks."""
import jax
import jax.numpy as jnp
import pytest

from bench.cell import load_cell
from bench.models import paper_cnn
from bench.peaks import peaks
from bench.models.paper_cnn import cnn_forward, cnn_init, xent

CONFIG = load_cell("sync-paper").config
WIDTHS = CONFIG["widths"]


def _cost_flops(fn, *args):
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


def test_forward_flops_match_compiler_count_at_batch_1():
    p = cnn_init(jax.random.PRNGKey(0), WIDTHS, jnp.float32)
    x = jnp.ones((1, 28, 28, 1))
    counted = _cost_flops(cnn_forward, p, x)
    ours = paper_cnn.forward_flops(CONFIG)
    # 134 and 64 taps an axis at 28 and 14 pixels (a 5-wide SAME kernel)
    assert ours == 2 * (134**2 * 32 + 64**2 * 32 * 64 + 3136 * 512 + 5120)
    # the compiler also counts bias, ReLU and pooling: under 1% here
    assert ours <= counted <= 1.01 * ours


def test_train_flops_match_compiler_count_at_batch_1():
    p = cnn_init(jax.random.PRNGKey(0), WIDTHS, jnp.float32)
    x, y = jnp.ones((1, 28, 28, 1)), jnp.zeros((1,), jnp.int32)
    counted = _cost_flops(jax.value_and_grad(xent), p, x, y)
    ours = paper_cnn.train_flops(CONFIG)
    fwd = paper_cnn.forward_flops(CONFIG)
    assert ours == 2 * fwd + 2 * (64**2 * 32 * 64 + 3136 * 512 + 5120)
    # within 2% of the compiler's count, which puts the first layer's
    # weight gradient at batch 1 lower than its taps
    assert abs(ours - counted) <= 0.02 * counted


def test_peaks_of_a_v5e_and_an_unknown_kind():
    v5e = peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device kind"):
        peaks("TPU v9 imaginary")
