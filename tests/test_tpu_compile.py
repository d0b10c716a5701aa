"""Every Pallas kernel on the main paths compiles for a TPU v5e chip.

The chip is described, not attached: the TPU compiler installed with jaxlib
lowers each kernel at gpt2-small widths for one device of a ``v5e:2x2``
topology and the compiled module must hold the Mosaic custom call.  That
catches what interpret-mode tests cannot -- block shapes the TPU tiling
rule refuses, unsupported in-kernel layouts, VMEM overruns.

The topology is described inside a fixture (never at import) and every
test skips from there when this installation cannot describe it.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import decode_attn as da
from repro.kernels import flash_attn as fa
from repro.kernels import int8_matmul as mm
from repro.kernels import opt_update as ou
from repro.kernels import qdq

F32, BF16, I8, I32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32

# gpt2-small: d_model 768, d_ff 3072, 12 heads x 64; a train step of
# batch 8 x seq 1024 feeds the linears M = 8192 token rows
M, D, FF, H, HD = 8192, 768, 3072, 12, 64
SLOTS, MAX_SEQ, PAGE, PREFILL = 4, 1024, 128, 128
# one fused-AdamW bucket: the 768 x 3072 MLP weight in 128-element blocks
ADAM_ROWS = D * FF // 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # lint: except-ok -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables can be written to the persistent
    # cache but never read back: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _codec(symmetric, sqrt_domain):
    return ou.MomentCodec(qmin=-128, qmax=127, symmetric=symmetric,
                          sqrt_domain=sqrt_domain)


#: name -> (kernel call with interpret mode off, [(shape, dtype), ...])
KERNELS = {
    "int8_matmul": (
        functools.partial(mm.int8_matmul, out_dtype=BF16),
        [((M, D), I8), ((D, FF), I8), ((M, 1), F32), ((1, FF), F32)]),
    "int8_matmul_nt": (
        functools.partial(mm.int8_matmul_nt, out_dtype=BF16),
        [((M, FF), BF16), ((D, FF), I8), ((1, FF), F32), ((M, 1), F32)]),
    "int8_matmul_tn": (
        mm.int8_matmul_tn,
        [((M, D), I8), ((M, FF), BF16), ((M, 1), F32), ((1, FF), F32)]),
    "qdq_row": (qdq.qdq_row, [((M, D), BF16)]),
    "qdq_scaled": (qdq.qdq_scaled, [((M, D), BF16), ((1, D), F32)]),
    "fused_adamw_blocks": (
        functools.partial(ou.fused_adamw_blocks,
                          m1_codec=_codec(True, False),
                          m2_codec=_codec(False, True),
                          weight_decay=True, interpret=False),
        [((ADAM_ROWS, 128), F32), ((ADAM_ROWS, 128), F32),
         ((ADAM_ROWS, 128), I8), ((ADAM_ROWS, 1), F32), ((ADAM_ROWS, 1), F32),
         ((ADAM_ROWS, 128), I8), ((ADAM_ROWS, 1), F32), ((ADAM_ROWS, 1), F32),
         ((len(ou.SCALARS),), F32)]),
    "decode_attention": (
        functools.partial(da.decode_attention, interpret=False),
        [((SLOTS, H, 1, HD), BF16), ((SLOTS, MAX_SEQ, H, HD), I8),
         ((SLOTS, MAX_SEQ, H, 1), F32), ((SLOTS, MAX_SEQ, H, HD), I8),
         ((SLOTS, MAX_SEQ, H, 1), F32), ((SLOTS, H, HD), BF16),
         ((SLOTS, H, HD), BF16), ((SLOTS,), I32)]),
    "decode_attention_paged": (
        functools.partial(da.decode_attention_paged, interpret=False),
        [((SLOTS, H, 1, HD), BF16), ((33, PAGE, H, HD), I8),
         ((33, PAGE, H, 1), F32), ((33, PAGE, H, HD), I8),
         ((33, PAGE, H, 1), F32), ((SLOTS, H, HD), BF16),
         ((SLOTS, H, HD), BF16), ((SLOTS,), I32),
         ((SLOTS, MAX_SEQ // PAGE), I32)]),
    "flash_attention_fwd": (
        functools.partial(fa.flash_attention_fwd, interpret=False),
        [((SLOTS * H, MAX_SEQ, HD), BF16)] * 3),
    "flash_attention_fwd_q8": (
        functools.partial(fa.flash_attention_fwd_q8, interpret=False),
        [((SLOTS, PREFILL, H, HD), BF16), ((SLOTS, MAX_SEQ, H, HD), I8),
         ((SLOTS, MAX_SEQ, H, 1), F32), ((SLOTS, MAX_SEQ, H, HD), I8),
         ((SLOTS, MAX_SEQ, H, 1), F32)]),
    "flash_attention_fwd_bwd": (
        jax.grad(lambda q, k, v: fa.flash_attention(
            q, k, v, True, 0, fa.BLOCK_Q, fa.BLOCK_K, False).astype(
                F32).sum(), argnums=(0, 1, 2)),
        [((SLOTS * H, MAX_SEQ, HD), BF16)] * 3),
}


#: the int8 matmuls' (M, K, N) in the benchmark's training cells (16,384
#: tokens; gpt2-small's four block linears, the cut Yi-6B's four) and in
#: int8 serving (64 slots padded to 128 rows, gpt2-small's qkv)
INT8_SHAPES = [(16384, 768, 2304), (16384, 768, 768), (16384, 768, 3072),
               (16384, 3072, 768), (16384, 4096, 4096), (16384, 4096, 512),
               (16384, 4096, 11008), (16384, 11008, 4096), (128, 768, 2304)]


def _int8_call(layout, m, k, n):
    """One int8 matmul with its picked tiles, as the training step calls it
    (bf16 activations and gradients, bf16 results)."""
    if layout == "fwd":
        return (functools.partial(mm.int8_matmul, out_dtype=BF16),
                [((m, k), I8), ((k, n), I8), ((m, 1), F32), ((1, n), F32)])
    if layout == "nt":
        return (functools.partial(mm.int8_matmul_nt, out_dtype=BF16),
                [((m, n), BF16), ((k, n), I8), ((1, n), F32), ((m, 1), F32)])
    return (functools.partial(mm.int8_matmul_tn, out_dtype=BF16),
            [((m, k), I8), ((m, n), BF16), ((m, 1), F32), ((1, n), F32)])


@pytest.mark.parametrize("layout", ["fwd", "nt", "tn"])
@pytest.mark.parametrize("m,k,n", INT8_SHAPES)
def test_int8_matmul_picked_tiles_compile_for_v5e(one_chip, layout, m, k, n):
    """The picked tiles (and the VMEM limit they raise) are ones the TPU
    compiler accepts at every shape the cells and serving run."""
    t = mm.pick_tiles(layout, m, k, n, BF16, None if layout == "fwd"
                      else BF16)
    assert (t.bm, t.bn, t.bk) != (128, 128, 128) or m == 128
    fn, shapes = _int8_call(layout, m, k, n)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (layout, t)


def test_int8_weight_grad_compiles_inside_a_layer_scan(one_chip):
    """A layer scan stacks each layer's weight gradient.  The TPU compiler
    would fuse the weight-gradient kernel into that update and hold the
    fusion to the default 16 MiB of scoped VMEM, below what the picked
    tiles ask; ``ops.int8_bwd_dw`` keeps the kernel a call of its own."""
    from repro.kernels import ops
    layers, m, k, n = 2, 16384, 768, 3072

    def stacked_dw(xq, xs, g):
        def body(carry, layer):
            return carry, ops.int8_bwd_dw(*layer, out_dtype=BF16,
                                          interpret=False)
        return jax.lax.scan(body, 0, (xq, xs, g))[1]

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in
            [((layers, m, k), I8), ((layers, m, 1), F32),
             ((layers, m, n), BF16)]]
    compiled = jax.jit(stacked_dw).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
