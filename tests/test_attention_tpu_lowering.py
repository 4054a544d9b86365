"""The pallas kernels the auto rules can select must LOWER for the TPU.

Every other kernel gate in tier-1 runs ``interpret=True`` on the CPU,
which never meets the TPU lowering's block-shape rules — that is how a
K/V BlockSpec with a one-head block in the second-minor position passed
every test and was refused at its first trace on a chip. Cross-lowering
from the CPU (``jax.export`` with ``platforms=["tpu"]``) runs those rules
without a chip; whether Mosaic then compiles the result is what
``chip_smoke.py`` proves on the device.
"""

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.nn.layers.attention import (
    _flash_attention,
    _paged_flash_attention,
    _should_use_flash,
    _should_use_flash_paged,
)


def _tpu_module(fn, *avals) -> str:
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*avals)
    return exported.mlir_module()


def _paged_avals(b, h, t, dh, nb, bt, ntab, dtype, pool_dtype):
    q = jax.ShapeDtypeStruct((b, h, t, dh), dtype)
    pool = jax.ShapeDtypeStruct((nb, bt, h, dh), pool_dtype)
    tab = jax.ShapeDtypeStruct((b, ntab), jnp.int32)
    row = jax.ShapeDtypeStruct((b,), jnp.int32)
    return (q, pool, pool, tab, tab, row, row, row, row)


# the flagship serving geometry (width 1024, 8 heads, block_tokens 16,
# 2048-token window): decode, a speculative verify chunk, one and two
# query tiles of prefill; H=4 is the local head count under tp=2. The
# engine keeps its pool at the master dtype, so the bf16 chunk meets a
# float32 pool there; an all-bf16 pool must lower too.
@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h", [8, 4])
@pytest.mark.parametrize("t", [1, 5, 128, 256])
def test_paged_kernel_lowers_for_tpu(h, t, pool_dtype):
    avals = _paged_avals(8, h, t, 128, 1200, 16, 130, jnp.bfloat16,
                         pool_dtype)
    text = _tpu_module(
        lambda *a: _paged_flash_attention(*a, tm=2048), *avals)
    assert "tpu_custom_call" in text


# the serving cell's own geometry (Cerebras-GPT-1.3B widths: 16 heads of
# 128, 48 slots, 1400 pool blocks of 16 tokens, a 129-entry walk), at
# every chunk length the auto rule admits there: decode, a verify chunk,
# the prompt buckets 32-2048. 129 is not whole compute blocks.
@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t", [1, 5, 8, 32, 64, 128, 256, 2048])
def test_paged_kernel_lowers_at_the_serving_cell(t, pool_dtype):
    avals = _paged_avals(48 if t <= 8 else 1, 16, t, 128, 1400, 16, 129,
                         jnp.bfloat16, pool_dtype)
    text = _tpu_module(
        lambda *a: _paged_flash_attention(*a, tm=2048), *avals)
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip: libtpu compiles for it,
    Mosaic included, which the cross-lowering above never reaches."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# Mosaic's own objections (a broadcast it cannot lay out, a scratch that
# does not fit) show only in a real compile: the decode and one-tile
# prefill programs of the serving cell, a verify chunk at the tp=4 local
# head count, and decode on an all-bf16 pool (since ISSUE 35 the
# serving cell's own), with a verify chunk on it
@pytest.mark.parametrize("b,h,t,pool_dtype", [
    (48, 16, 1, jnp.float32), (1, 16, 128, jnp.float32),
    (8, 4, 5, jnp.float32), (48, 16, 1, jnp.bfloat16),
    (48, 16, 5, jnp.bfloat16)])
def test_paged_kernel_compiles_for_v5e(one_chip, b, h, t, pool_dtype):
    text = _compile_for(
        one_chip, lambda *a: _paged_flash_attention(*a, tm=2048),
        *_paged_avals(b, h, t, 128, 1400, 16, 129, jnp.bfloat16,
                      pool_dtype))
    assert "tpu_custom_call" in text


# grouped KV heads (PR 31): a KV head's query heads ride its tile. The
# Trinity cell's geometry (48 query / 8 KV heads of 128, bf16 pool, 32
# slots): decode on the window kind (a 259-entry walk) and on the full
# kind (1,026 entries), and one 1,024-token admission chunk (eight query
# tiles, one MXU product a query head); the hybrid cell's group of 4 at
# its 64 slots
@pytest.mark.parametrize("b,hq,hk,t,tm,nb,ntab", [
    (32, 48, 8, 1, 4096, 10344, 258), (32, 48, 8, 1, 16384, 32920, 1026),
    (1, 48, 8, 1024, 4096, 10344, 322), (64, 32, 8, 1, 2048, 8192, 130)])
def test_grouped_paged_kernel_compiles_for_v5e(one_chip, b, hq, hk, t, tm,
                                               nb, ntab):
    q, pool, _, tab, _, row = _paged_avals(
        b, hk, t, 128, nb, 16, ntab, jnp.bfloat16, jnp.bfloat16)[:6]
    q = jax.ShapeDtypeStruct((b, hq, t, 128), jnp.bfloat16)
    text = _compile_for(
        one_chip, lambda *a: _paged_flash_attention(*a, tm=tm),
        q, pool, pool, tab, tab, row, row, row, row)
    assert "tpu_custom_call" in text


# EVA's two calls a layer (PR 41) at the evabyte cell's geometry (32
# ungrouped heads of 128, bf16 pools of 16-entry blocks, 8 slots): the
# aligned window's causal walk (196 ring entries) and the summaries'
# walk without a causal edge (72), each handing back its sums, for a
# decode step and for a 1,024-token admission chunk (eight query tiles)
@pytest.mark.parametrize("b,t", [(8, 1), (1, 1024)])
@pytest.mark.parametrize("causal,tm,nb,ntab", [
    (True, 2048, 1056, 130), (False, 72 * 16, 544, 72)])
def test_eva_forms_of_the_paged_kernel_compile_for_v5e(
        one_chip, b, t, causal, tm, nb, ntab):
    if causal and t > 1:
        ntab = 194       # a window and a chunk of entries
    avals = _paged_avals(b, 32, t, 128, nb, 16, ntab, jnp.bfloat16,
                         jnp.bfloat16)
    text = _compile_for(
        one_chip, lambda *a: _paged_flash_attention(
            *a, tm=tm, causal=causal, stats=True), *avals)
    assert "tpu_custom_call" in text
    assert "f32[%d,32,%d,128]" % (b, t) in text.replace(" ", "")


def _compile_for(one_chip, fn, *avals):
    """``fn`` compiled for the described chip; its HLO text. A described
    device's executable cannot be read back from the persistent cache:
    keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    avals = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
             for a in avals]
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn).lower(*avals).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


# the hybrid cell's two Pallas kernels at its published widths
# (granite-4.0-h-small: 128 Mamba-2 heads of 64, state 128, 64 slots;
# 36 held experts of 4096 x 1536 and 768 x 4096): the one-step state
# update, and the grouped expert product at decode rows (64 slots x 10
# picks) and at a 2,048-token prefill's
def test_ssm_step_kernel_compiles_for_v5e(one_chip):
    from deeplearning4j_tpu.nn.layers.mamba2 import _ssm_step_update

    f32 = jnp.float32
    S = jax.ShapeDtypeStruct
    text = _compile_for(
        one_chip, _ssm_step_update, S((64, 128, 64, 128), f32),
        S((64, 128, 128), f32), S((64, 128, 128), f32),
        S((64, 1, 64, 128), f32), S((64, 1, 64, 128), f32),
        S((64,), jnp.int32))
    assert "_ssm_step_update" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [640, 20480])
def test_grouped_expert_product_compiles_for_v5e(one_chip, rows):
    from deeplearning4j_tpu.nn.layers.moe import _moe_grouped_product

    bf16 = jnp.bfloat16
    S = jax.ShapeDtypeStruct

    def both(xs, w_in, w_out, sizes):
        gu = _moe_grouped_product(xs, w_in, sizes)
        return _moe_grouped_product(gu[:, :768], w_out, sizes)

    text = _compile_for(
        one_chip, both, S((rows, 4096), bf16), S((36, 4096, 1536), bf16),
        S((36, 768, 4096), bf16), S((36,), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') >= 2


def test_the_expert_layers_training_passes_stay_in_place_on_v5e(one_chip):
    """The LFM2 cell's expert layer under a gradient (16,384 tokens of
    2,048, top 4 of 32 outputs, 8 held experts of 2 x 1,792), compiled
    for the described chip: the four passes around the grouped products
    are loops over slabs of the held pairs' rows whose every write is
    an in-place update. No pass copies an array of all the pairs' rows
    (a loop that read its own input again between two writes did: 470 MB
    a trip); three of the four buffers start uninitialised
    (``AllocateBuffer``: nothing zeroes them), each inside a branch of
    its conditional and not at the top of the program, where it would be
    held from the first instruction on; the fourth is the dead ``gu``."""
    import re

    from deeplearning4j_tpu.nn.layers import moe

    bf16, S = jnp.bfloat16, jax.ShapeDtypeStruct
    m, d, f, top_k = 16384, 2048, 1792, 4

    def loss(p, x, probe):
        y, counts = moe.dropless_moe(
            p, x, top_k=top_k, experts_held=(0, 8), kernel=True,
            gate_rule="sigmoid_bias", route_eps=1e-6, detach_scores=True)
        # (not linear in y: as in a step, the value's cotangent waits
        # for the value, so every forward reader of ``ys`` is done)
        return jnp.sum(jnp.square((y * probe).astype(jnp.float32))), counts

    def grads(router, bias, w_in, w_out, x, probe):
        p = {"router": router, "expert_bias": bias, "We_in": w_in,
             "We_out": w_out}
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            p, x, probe)

    text = _compile_for(
        one_chip, grads, S((d, 32), bf16), S((32,), jnp.float32),
        S((8, d, 2 * f), bf16), S((8, f, d), bf16), S((m, d), bf16),
        S((m, d), bf16))
    pairs = rf"bf16\[{m * top_k},(?:{d}|{f}|{2 * f})\]"
    assert re.findall(rf"{pairs}[^\n]* copy\(", text) == []
    made = re.findall(
        rf'{pairs}[^\n]*custom_call_target="AllocateBuffer"[^\n]*', text)
    assert len(made) == 6 and all("/cond/branch_" in line for line in made)
    assert text.count(" conditional(") == 3
    slabs = [line for line in text.splitlines()
             if " while(" in line and "/while" in line
             and "searchsorted" not in line]
    assert len(slabs) == 4
    assert text.count('custom_call_target="tpu_custom_call"') >= 6


def test_paged_auto_rule_only_selects_shapes_that_lower(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for t in (1, 5, 8, 64, 128, 256, 2048):
        assert _should_use_flash_paged(None, 16, 128, t), t
    # a long chunk that is not whole query tiles stays on the gather
    # program, as do geometries below the native tile
    assert not _should_use_flash_paged(None, 16, 128, 200)
    assert not _should_use_flash_paged(None, 4, 128, 1)
    assert not _should_use_flash_paged(None, 16, 32, 1)
    with pytest.raises(ValueError, match="query"):
        _should_use_flash_paged(True, 16, 128, 200)


# the two training cells' geometries (Cerebras-GPT-1.3B: 8 x 2048, 16
# heads of 128; LFM2-8B-A1B: 2 x 8192, 32 query heads of 64 over 8 KV
# heads, a KV head at a time over its group): the block-sparse forward
# and its ONE fused backward kernel (dQ, dK, dV from one pass over S)
@pytest.mark.parametrize("b,h,hk,t,dh", [
    (8, 16, 16, 2048, 128), (2, 32, 8, 8192, 64)])
def test_flash_kernel_lowers_for_tpu_forward_and_backward(
        monkeypatch, b, h, hk, t, dh):
    q = jax.ShapeDtypeStruct((b, h, t, dh), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, hk, t, dh), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(_flash_attention(q, k, v, True)
                       .astype(jnp.float32))

    text = _tpu_module(jax.grad(loss, argnums=(0, 1, 2)), q, k, k)
    assert text.count("tpu_custom_call") == 2
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _should_use_flash(None, q, None)


# numerics, through the Pallas interpreter at a small size: output and
# the three gradients against the plain program, heads of 128 and of
# 64, causal and not, grouped KV heads and not; T = 1536 is three tiles
# a side, so fully masked tiles are skipped and only the diagonal ones
# mask
@pytest.mark.parametrize("t,dh,causal,hk", [
    (512, 128, True, 4), (512, 128, False, 4), (512, 64, True, 4),
    (512, 64, False, 4), (512, 128, True, 2), (512, 128, False, 2),
    (512, 64, True, 1), (512, 64, False, 2), (1536, 128, True, 4)])
def test_flash_kernel_agrees_with_the_dense_program(t, dh, causal, hk):
    from deeplearning4j_tpu.nn.layers.attention import (
        _dense_attention,
        _repeat_kv_heads,
    )

    b, h = (1, 4)
    keys = jax.random.split(jax.random.PRNGKey(t + dh + hk), 4)
    q, w = (jax.random.normal(key, (b, h, t, dh)) for key in keys[:2])
    k, v = (jax.random.normal(key, (b, hk, t, dh)) for key in keys[2:])

    def flash(q, k, v):
        return _flash_attention(q, k, v, causal, interpret=True)

    def dense(q, k, v):
        return _dense_attention(q, *_repeat_kv_heads(q, k, v), causal,
                                None, None)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2)))(q, k, v)

    out = jax.jit(flash)(q, k, v)
    assert out.shape == q.shape and out.dtype == q.dtype
    assert jnp.max(jnp.abs(out - dense(q, k, v))) < 2e-5
    for got, want in zip(grads(flash), grads(dense)):
        assert got.shape == want.shape
        assert jnp.max(jnp.abs(got - want)) < 2e-5
