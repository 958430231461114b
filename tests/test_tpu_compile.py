"""Kernels of the benchmark's main path compiled FOR the TPU v5e, by the
TPU's own compiler, at the widths the cells run: what Mosaic refuses (a slice
off the tiling, a cast it has no rule for, more VMEM than a kernel may have)
shows here and costs no chip time.  Nothing runs, so nothing here is a result
or a speed.

The TPU's library belongs to one process at a time: the topology is described
inside a fixture, by the one worker that is given this file, and every such
compile lives in this file (``on-chip-measurement`` guide, section 2)."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops import state_space
from mxnet_tpu.ops.pallas.selective_scan import selective_scan


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    held = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", held)
    compilation_cache.reset_cache()


# phi4_mini_flash.train_s4096: one sequence of 4,096 positions, D 5120, N 16,
# bf16 x, b and c beside a float32 dt
T, DIM, N = 4096, 5120, 16


@pytest.mark.parametrize("state", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16_control"])
def test_scan_kernels_compile_for_v5e(one_chip, no_compile_cache, state):
    """Forward and backward at the cell's shape and the module's chunk; the
    bf16 state is the precision control of ``tests_tpu/test_sambay_tpu.py``,
    which has to compile on the chip to be a control.  The program holds the
    chunk-boundary states and never the ``[T, N, D]`` tensor (1.3 GB)."""
    t, dim, n = T, DIM, N

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def total(x, dt, a, b, c):
        return selective_scan(x, dt, a, b, c, chunk=state_space.SCAN_CHUNK,
                              state_dtype=state, interpret=False).sum()
    compiled = jax.jit(jax.grad(total, range(5))).trace(
        shape((1, t, dim), jnp.bfloat16), shape((1, t, dim), jnp.float32),
        shape((dim, n), jnp.float32), shape((1, t, n), jnp.bfloat16),
        shape((1, t, n), jnp.bfloat16)).lower(
        lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert "selective_scan_fwd" in text and "selective_scan_bwd" in text
    assert text.count("tpu_custom_call") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < t * n * dim * 4 / 4


# mellum2_12b_a2_5b.train_s8192: one sequence of 8,192 positions, 32 query
# and 4 K/V heads of 128, 8 assignments a token over 16 held experts of 64
def test_flash_kernels_compile_for_v5e_at_heads_of_128(one_chip,
                                                       no_compile_cache):
    """Forward and both backward kernels with a group of 8 query heads a K/V
    head and heads of 128, the block the decoders give, bf16."""
    from mxnet_tpu.gluon.model_zoo._attention import _flash_block
    from mxnet_tpu.ops.pallas import flash_attention
    t, block = 8192, _flash_block(8192)

    def shape(heads):
        return jax.ShapeDtypeStruct((heads, t, 128), jnp.bfloat16,
                                    sharding=one_chip)

    def total(q, k, v):
        return flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block,
            interpret=False).astype(jnp.float32).sum()
    compiled = jax.jit(jax.grad(total, range(3))).trace(
        shape(32), shape(4), shape(4)).lower(
        lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkv"):
        assert kernel in text


# kanana2_30b_a3b.train_s8192: latent attention, 32 heads of 192 for Q and K
# and of 128 for V over 8,192 positions
def test_flash_kernels_compile_for_v5e_with_a_v_head_of_128_beside_192(
        one_chip, no_compile_cache):
    """The three causal kernels with Q and K heads of 192, which are not a
    multiple of the 128 lanes, and V and output heads of 128, unpadded, at
    the block the decoders give, bf16."""
    from mxnet_tpu.gluon.model_zoo._attention import _flash_block
    from mxnet_tpu.ops.pallas import flash_attention
    t, block = 8192, _flash_block(8192)

    def shape(d):
        return jax.ShapeDtypeStruct((32, t, d), jnp.bfloat16,
                                    sharding=one_chip)

    def total(q, k, v):
        return flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block,
            interpret=False).astype(jnp.float32).sum()
    compiled = jax.jit(jax.grad(total, range(3))).trace(
        shape(192), shape(192), shape(128)).lower(
        lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkv"):
        assert kernel in text
    assert "bf16[32,8192,128]" in text and "bf16[32,8192,192]" in text


# the two decoders' window layers: mellum2_12b_a2_5b.train_s8192 (32 query and
# 4 K/V heads of 128, a window of 1,024 over 8,192 positions) and
# phi4_mini_flash.train_s4096 (40 and 20 heads of 64, 512 over 4,096)
@pytest.mark.parametrize("heads,kv_heads,t,d,window", [
    (32, 4, 8192, 128, 1024), (40, 20, 4096, 64, 512)],
    ids=["mellum2_heads_of_128", "phi4_heads_of_64"])
def test_window_kernels_compile_for_v5e(one_chip, no_compile_cache, heads,
                                        kv_heads, t, d, window):
    """The three windowed kernels, whose grid walks the band, at the block
    ``ops.window_attention`` gives, bf16: the index arithmetic of their
    block maps and the steps skipped at the sequence's edge are Mosaic's to
    accept."""
    from mxnet_tpu.ops.attention import _window_block
    from mxnet_tpu.ops.pallas import flash_attention
    block = _window_block(t)

    def shape(n):
        return jax.ShapeDtypeStruct((n, t, d), jnp.bfloat16,
                                    sharding=one_chip)

    def total(q, k, v):
        return flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block,
            interpret=False, window=window).astype(jnp.float32).sum()
    compiled = jax.jit(jax.grad(total, range(3))).trace(
        shape(heads), shape(kv_heads), shape(kv_heads)).lower(
        lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    for kernel in ("window_attention_fwd", "window_attention_bwd_dq",
                   "window_attention_bwd_dkv"):
        assert kernel in text
    assert "flash_attention_" not in text


# (d, F, held, routed, k, the router's own arguments) of the cells that run
# the dropless expert layer
EXPERT_LAYERS = {
    "mellum2_12b_a2_5b": (2304, 896, 16, 64, 8, {}),
    "lfm2_8b_a1b": (2048, 1792, 8, 32, 4,
                    {"score": "sigmoid", "eps": 1e-6, "bias": True}),
    "kanana2_30b_a3b": (2048, 768, 16, 128, 6,
                        {"score": "sigmoid", "eps": 1e-20, "scale": 2.448,
                         "bias": True})}


@pytest.mark.parametrize("config", sorted(EXPERT_LAYERS))
@pytest.mark.parametrize("router", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16_control"])
def test_dropless_expert_layer_compiles_for_v5e(one_chip, no_compile_cache,
                                                monkeypatch, router, config):
    """Routing (softmax, or sigmoid scores chosen by score + bias), sort, the
    two grouped products and their gradients at a cell's widths, one
    sequence: Mosaic has to take the three grouped-product kernels at the
    cell's widths and tiles, and the row passes around them, whose trip
    count is read on the device, have to stay loops or Mosaic kernels.  The
    bf16 router is the precision control of
    ``tests_tpu/test_moe_decoder_tpu.py`` and ``test_lfm2_moe_tpu.py``."""
    from mxnet_tpu.ops.registry import get_op
    from mxnet_tpu.parallel import moe
    from mxnet_tpu.ops.pallas import gated_rows, grouped_matmul, token_rows
    monkeypatch.setattr(moe, "_ROUTER_DTYPE", router)
    for module in (gated_rows, grouped_matmul, token_rows):
        monkeypatch.setattr(module, "on_tpu", lambda: True)
    n = 8192
    d, f, held, routed, k, how = EXPERT_LAYERS[config]
    how = dict(how)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    bias = (shape(routed, dtype=jnp.float32),) if how.pop("bias", False) \
        else ()

    def total(tokens, router, gate_up, down, *bias):
        out, _ = get_op("moe_dropless_ffn")(
            tokens, router, gate_up, down, *bias, num_experts=routed,
            first_expert=0, k=k, **how)
        return out.astype(jnp.float32).sum()
    compiled = jax.jit(jax.grad(total, range(4))).trace(
        shape(n, d), shape(d, routed), shape(held, d, 2 * f),
        shape(held, f, d), *bias).lower(lowering_platforms=("tpu",)).compile()
    # the N x k rows through both products, forward and backward, and no
    # (N, E, C) dispatch tensor: under 3 GB of temporaries
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9
    text = compiled.as_text()
    assert "ragged-dot" not in text
    for kernel in ("moe_grouped_fwd", "moe_grouped_dx", "moe_grouped_dw"):
        assert text.count(f"/{kernel}/pallas_call") == 2, kernel
    # the bounded row passes: the gather of the output's gradient stays a
    # loop for the TPU; the stage between the products is its two kernels,
    # the token-side passes the two of ``token_rows``: the sum of the gather
    # in's backward pass (the forward's is dead: a sum's gradient does not
    # read its value) and the gates' gradient
    assert text.count(" while(") == 1
    assert "moe_gated_fwd" in text and "moe_gated_bwd" in text
    assert text.count("/moe_token_sum/pallas_call") == 1
    assert text.count("/moe_token_dot/pallas_call") == 1


def test_expert_layers_share_one_lowering_a_kernel(one_chip, no_compile_cache,
                                                  monkeypatch):
    """Two recomputed expert layers at mellum2_12b_a2_5b's widths, forward
    and backward, compiled for a v5e: their grouped products come to many
    call sites (each layer's forward, its recomputation, the input and
    weight gradients) but six distinct Mosaic payloads, two widths x
    forward, input gradient and weight gradient (and the two of the
    gated rows, and the token sum, weighted or not, and dot): what a process
    lowers, and what its executable holds again at each site."""
    import re

    from mxnet_tpu.ops.registry import get_op
    from mxnet_tpu.ops.pallas import gated_rows, grouped_matmul, token_rows
    for module in (gated_rows, grouped_matmul, token_rows):
        monkeypatch.setattr(module, "on_tpu", lambda: True)
    n = 8192
    d, f, held, routed, k, _ = EXPERT_LAYERS["mellum2_12b_a2_5b"]

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    @jax.checkpoint
    def layer(tokens, router, gate_up, down):
        return get_op("moe_dropless_ffn")(tokens, router, gate_up, down,
                                          num_experts=routed, k=k)[0]

    def total(tokens, router, gate_up, down):
        for _ in range(2):
            tokens = tokens + layer(tokens, router, gate_up, down)
        return tokens.astype(jnp.float32).sum()
    lowered = jax.jit(jax.grad(total, range(4))).trace(
        shape(n, d), shape(d, routed), shape(held, d, 2 * f),
        shape(held, f, d)).lower(lowering_platforms=("tpu",))
    payloads = re.findall(r'stablehlo\.custom_call @tpu_custom_call\(.*?'
                          r'backend_config = "(.*?)"', lowered.as_text())
    assert len(payloads) > len(set(payloads)) == 6 + 2 + 2
    text = lowered.compile().as_text()
    sites = re.findall(r'/(moe_grouped_\w+)/pallas_call', text)
    assert sorted(set(sites)) == ["moe_grouped_dw", "moe_grouped_dx",
                                  "moe_grouped_fwd"]
    assert sites.count("moe_grouped_dx") == sites.count("moe_grouped_dw") \
        == 2 * 2


# (N, k, d, held) of the three expert cells' steps: mellum2_12b_a2_5b and
# kanana2_30b_a3b two sequences of 8,192, lfm2_8b_a1b four
TOKEN_SIDE = {"mellum2_12b_a2_5b": (16384, 8, 2304, 16),
              "lfm2_8b_a1b": (32768, 4, 2048, 8),
              "kanana2_30b_a3b": (16384, 6, 2048, 16)}


@pytest.mark.parametrize("config", sorted(TOKEN_SIDE))
def test_token_kernels_compile_for_v5e(one_chip, no_compile_cache, config):
    """``moe_token_sum`` and ``moe_token_dot`` and the token map at a cell's
    step, bf16 rows of the N x k buffer in HBM: Mosaic has to take the
    8-row chunk copies, the two sets of slots in VMEM and the products over
    them."""
    from mxnet_tpu.ops.pallas import token_rows
    n, k, d, held = TOKEN_SIDE[config]

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def passes(rows, back, offsets, weights, dy):
        tmap = token_rows.token_map(back, offsets)
        return (token_rows._reduce(rows, tmap, weights, dot=False,
                                   interpret=False),
                token_rows._reduce(rows, tmap, dy, dot=True,
                                   interpret=False))
    compiled = jax.jit(passes).trace(
        shape(n * k, d), shape(n, k, dtype=jnp.int32),
        shape(held + 1, dtype=jnp.int32), shape(n, k, dtype=jnp.float32),
        shape(n, d)).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert "moe_token_sum" in text and "moe_token_dot" in text
    # no [N, k, d] tensor and no gather of the rows
    assert f"bf16[{n},{k},{d}]" not in text and " gather(" not in text
