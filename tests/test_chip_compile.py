"""Ask the v5e's compiler, without a chip, for every kernel the TPU runs.

The TPU compiler is installed here and compiles for a chip that is
described and not attached (``jax.experimental.topologies``). Interpret
mode cannot show what it shows: every PR-16 kernel had passed its
interpret-mode parity tests and four of five families were refused here
(VMEM overflow, a bf16 vector compare, an in-kernel gather, a
rank-changing reshape). So each kernel the automatic TPU rule selects
(``perf.pallas.TPU_AUTO_FAMILIES``), plus flash attention, the Word2Vec
scatter and the routed experts' grouped products, is compiled
``interpret=False`` at one main-path shape;
the BN family — outside the automatic rule — is compiled where its
``supported()`` says it fits, and must refuse what does not.

A compile that passes is not a chip run: nothing executes. The chip run is
``chip_smoke.py``'s ``kernels`` phase. The name sorts early so the tier-1
time cap cannot cut this file.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.perf import pallas as pk
from deeplearning4j_tpu.perf.pallas import adc, bn

F32, BF16, I8, U8, I32 = (jnp.float32, jnp.bfloat16, jnp.int8, jnp.uint8,
                          jnp.int32)


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip; the module is skipped where the topology
    cannot be described. The persistent compile cache is off around these
    compiles: an executable for a described chip can be written to it but
    not read back without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_backend(monkeypatch):
    """The code under test asks ``jax.default_backend()`` and would take
    its CPU branch here; the test steers it, not an option of the
    program. With this the AUTOMATIC rule resolves as it does on a chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not pk.interpret()


def _pq(S):
    q, cb, codes = S((64, 64), F32), S((8, 256, 8), F32), S((1 << 20, 8), U8)
    assert pk.take("adc_pq", adc.pq_supported(q, cb, codes))
    return lambda q, cb, codes: adc.score_pq(q, cb, codes, k=16), (q, cb,
                                                                   codes)


def _int4_table(S):
    n = 1 << 20
    args = (S((64, 64), F32), S((n, 32), I8), S((n,), F32), S((n,), F32))
    assert pk.take("int4_dot", adc.brute_int4_supported(*args))
    return (lambda q, p, vn, sv: adc.score_brute_int4(
        q, p, vn, sv, k=16, metric="euclidean")), args


def _int4_weights(S):
    # the ResNet50 head (2048 -> 1000) at the serving ladder's top rung,
    # through the int4-weight call site itself
    from deeplearning4j_tpu.quant.lowering import _dense_int4_acc
    assert adc.int4_supported(32, 1000, 1024)
    return (lambda xq, wq: _dense_int4_acc(xq, wq, 2048)), (
        S((32, 2048), I8), S((1000, 1024), I8))


def _flash(S):
    from deeplearning4j_tpu.parallel.ring_attention import \
        flash_self_attention
    qkv = S((4, 8, 1024, 64), BF16)
    return (lambda q, k, v: flash_self_attention(q, k, v, causal=True)), (
        qkv, qkv, qkv)


def _scatter(S):
    from deeplearning4j_tpu.nlp.pallas_scatter import scatter_add_pallas
    return scatter_add_pallas, (S((20000, 100), F32), S((8192,), I32),
                                S((8192, 100), F32))


def _grouped_experts(S):
    # the routed layer's three grouped products and their backward pass at
    # the Kimi Linear share's widths: 8192 tokens x top-8 rows, 8 experts
    # of 2304 x 1024 (the megablox kernel, through the layer's own call)
    from deeplearning4j_tpu.nn.conf.experts import grouped_matmul

    def fwd_bwd(rows, w_gate, w_down, sizes):
        def f(rows, w_gate, w_down):
            hidden = jax.nn.silu(grouped_matmul(rows, w_gate, sizes))
            return jnp.sum(grouped_matmul(hidden, w_down, sizes)
                           .astype(F32))
        return jax.grad(f, argnums=(0, 1, 2))(rows, w_gate, w_down)

    return fwd_bwd, (S((65536, 2304), BF16), S((8, 2304, 1024), BF16),
                     S((8, 1024, 2304), BF16), S((8,), I32))


def _kda_scan(S):
    # Kimi Delta Attention's chunked scan at the Kimi Linear share's shape,
    # one sequence of 8192 steps, 32 heads of 128: the forward kernel that
    # saves the chunks' entry states, solved u and scores, and the backward
    # kernel that reads them, through chunked_kda's own selection
    from deeplearning4j_tpu.nn.conf.linear_attention import chunked_kda
    from deeplearning4j_tpu.perf.pallas import kda
    shape = (1, 8192, 32, 128)
    args = (S(shape, BF16),) * 3 + (S(shape, F32), S(shape[:3], F32))
    assert pk.take("kda_scan", kda.supported(*args, 64, 8))

    def fwd_bwd(*a):
        return jax.grad(lambda *a: jnp.sum(chunked_kda(*a)),
                        argnums=range(5))(*a)

    return fwd_bwd, args, ("kda_scan_fwd", "kda_scan_bwd")


def _kda_scan_float32(S):
    # the same kernels with every product in float32 (what they compile to
    # under jax.default_matmul_precision("highest")), float32 inputs, a
    # head count that one grid step takes whole
    from deeplearning4j_tpu.nn.conf.linear_attention import chunked_kda
    shape = (2, 1024, 6, 128)
    args = (S(shape, F32),) * 4 + (S(shape[:3], F32),)

    def fwd_bwd(*a):
        with jax.default_matmul_precision("highest"):
            return jax.grad(lambda *a: jnp.sum(chunked_kda(*a)),
                            argnums=range(5))(*a)

    return fwd_bwd, args, ("kda_scan_fwd", "kda_scan_bwd")


def _blocked_attention(S):
    # latent attention at the Kimi Linear share's shape, one sequence of
    # 8192 steps, 32 q/k heads of 192 and v heads of 128 in bfloat16: the
    # forward kernel that saves the log-sum-exp and the backward
    # kernel, through blocked_causal_attention's own selection
    from deeplearning4j_tpu.nn.conf.attention import blocked_causal_attention
    from deeplearning4j_tpu.perf.pallas import attention
    args = (S((1, 32, 8192, 192), BF16),) * 2 + (S((1, 32, 8192, 128), BF16),)
    assert pk.take("blocked_attention", attention.supported(*args, 512))

    def fwd_bwd(*a):
        return jax.grad(lambda *a: jnp.sum(blocked_causal_attention(
            *a, 512).astype(F32)), argnums=(0, 1, 2))(*a)

    return fwd_bwd, args, ("mla_attend_fwd", "mla_attend_bwd")


def _blocked_attention_float32(S):
    # the same kernels on float32 inputs, a batch of two, a head count one
    # grid step does not take whole, a length that is padded (1000 -> 1024)
    from deeplearning4j_tpu.nn.conf.attention import blocked_causal_attention
    args = (S((2, 6, 1000, 192), F32),) * 2 + (S((2, 6, 1000, 128), F32),)

    def fwd_bwd(*a):
        return jax.grad(lambda *a: jnp.sum(blocked_causal_attention(
            *a, 256)), argnums=(0, 1, 2))(*a)

    return fwd_bwd, args, ("mla_attend_fwd", "mla_attend_bwd")


def _kda_scan_scalar_decay(S):
    # Gated DeltaNet at the Qwen3-Next share's shape: ONE decay a value
    # head spread over its 128 channels in front of the kernels, 16 q/k
    # heads repeated to the 32 value heads, one sequence of 8192 steps
    from deeplearning4j_tpu.nn.conf.linear_attention import chunked_kda
    args = ((S((1, 8192, 16, 128), BF16),) * 2 + (S((1, 8192, 32, 128), BF16),)
            + (S((1, 8192, 32), F32),) * 2)

    def fwd_bwd(*a):
        def scan(q, k, v, g, b):
            q, k = (jnp.repeat(x, 2, axis=2) for x in (q, k))
            return jnp.sum(chunked_kda(
                q, k, v, jnp.broadcast_to(g[..., None], q.shape), b))
        return jax.grad(scan, argnums=range(5))(*a)

    return fwd_bwd, args, ("kda_scan_fwd", "kda_scan_bwd")


def _blocked_attention_grouped(S):
    # gated attention at the Qwen3-Next share's shape: 16 query heads of
    # 256 over 2 k/v heads repeated in front of the kernels, one sequence
    # of 8192 steps in bfloat16; the backward kernel keeps four heads' dq
    # (8192 x 256 float32 each) in VMEM
    from deeplearning4j_tpu.nn.conf.attention import blocked_causal_attention
    from deeplearning4j_tpu.perf.pallas import attention
    q, kv = S((1, 16, 8192, 256), BF16), S((1, 2, 8192, 256), BF16)
    assert pk.take("blocked_attention", attention.supported(q, q, q, 512))

    def fwd_bwd(q, k, v):
        def attend(q, k, v):
            k, v = (jnp.repeat(x, 8, axis=1) for x in (k, v))
            return jnp.sum(blocked_causal_attention(q, k, v, 512).astype(F32))
        return jax.grad(attend, argnums=(0, 1, 2))(q, k, v)

    return fwd_bwd, (q, kv, kv), ("mla_attend_fwd", "mla_attend_bwd")


def _blocked_attention_rotary(S):
    # the looped block's attention at the Ouro stage's shape, through the
    # layer: 16 query and 16 key/value heads of 128 (equal q, k and v
    # widths, no repeat), every width rotated, one sequence of 8192 steps
    # in bfloat16
    from deeplearning4j_tpu.nn.conf.attention import RotaryAttention
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    layer = RotaryAttention(n_heads=16, head_dim=128, rope_theta=1e6)
    shapes = jax.eval_shape(lambda k: layer.init(
        k, InputType.recurrent(2048, 8192), BF16)[0], jax.random.key(0))
    params = {k: S(a.shape, BF16) for k, a in shapes.items()}

    def fwd_bwd(params, x):
        return jax.grad(lambda p, x: jnp.sum(layer.apply(
            p, {}, x)[0].astype(F32)), argnums=(0, 1))(params, x)

    return (fwd_bwd, (params, S((1, 8192, 2048), BF16)),
            ("mla_attend_fwd", "mla_attend_bwd"))


def _mellum_attention(S, sliding):
    # one attention layer of the Mellum2 share, through the layer: 32 query
    # heads over 4 key/value heads of 128 repeated in front of the kernels,
    # per-head q/k norms, one sequence of 16,384 steps in bfloat16. A
    # sliding layer runs the BAND of tile pairs under a window of 1024 (93
    # of 528 at tiles of 512) and turns by the plain frequencies; the full
    # layer runs the triangle at its first 16k shape (the backward kernel
    # keeps four heads' dq of 16,384 x 128 float32 in VMEM) and turns by
    # the YaRN-scaled ones
    from deeplearning4j_tpu.nn.conf.attention import RotaryAttention
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.perf.pallas import attention
    yarn = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782}
    layer = RotaryAttention(n_heads=32, n_kv_heads=4, head_dim=128,
                            rope_theta=5e5, qk_norm=True,
                            window=1024 if sliding else 0,
                            rope_scaling=None if sliding else yarn)
    q = S((1, 32, 16384, 128), BF16)
    assert pk.take("blocked_attention", attention.supported(
        q, q, q, 512, 1024 if sliding else None))
    shapes = jax.eval_shape(lambda k: layer.init(
        k, InputType.recurrent(2304, 16384), BF16)[0], jax.random.key(0))
    params = {k: S(a.shape, BF16) for k, a in shapes.items()}

    def fwd_bwd(params, x):
        return jax.grad(lambda p, x: jnp.sum(layer.apply(
            p, {}, x)[0].astype(F32)), argnums=(0, 1))(params, x)

    return (fwd_bwd, (params, S((1, 16384, 2304), BF16)),
            ("mla_attend_fwd", "mla_attend_bwd"))


def _blocked_attention_band(S):
    return _mellum_attention(S, True)


def _blocked_attention_16k(S):
    return _mellum_attention(S, False)


def _blocked_attention_band_float32(S):
    # the band on float32 inputs with a window that is no multiple of the
    # tile (300 keys in tiles of 256: the far edge crosses two tiles of a
    # query tile), a batch of two, a padded length
    from deeplearning4j_tpu.nn.conf.attention import blocked_causal_attention
    args = (S((2, 6, 1000, 128), F32),) * 3

    def fwd_bwd(*a):
        return jax.grad(lambda *a: jnp.sum(blocked_causal_attention(
            *a, 256, 300)), argnums=(0, 1, 2))(*a)

    return fwd_bwd, args, ("mla_attend_fwd", "mla_attend_bwd")


def _grouped_experts_16(S):
    # the routed layer's grouped products and their backward pass at the
    # Mellum2 share's widths: 16,384 tokens x top-8 of 64, a quarter held
    # here: a window of 65,536 sorted slots (half of them all,
    # ``_window_slots``) of which some 32,768 are rows of the 16 experts of
    # 2304 x 896 (2,048 rows an expert; 896 = 7 x 128)
    from deeplearning4j_tpu.nn.conf.experts import grouped_matmul

    def fwd_bwd(rows, w_gate, w_down, sizes):
        def f(rows, w_gate, w_down):
            hidden = jax.nn.silu(grouped_matmul(rows, w_gate, sizes))
            return jnp.sum(grouped_matmul(hidden, w_down, sizes)
                           .astype(F32))
        return jax.grad(f, argnums=(0, 1, 2))(rows, w_gate, w_down)

    return fwd_bwd, (S((65536, 2304), BF16), S((16, 2304, 896), BF16),
                     S((16, 896, 2304), BF16), S((16,), I32))


def _blocked_attention_64_wide(S):
    # the one attention layer of the LFM2 share, through the layer: 32 query
    # heads over 8 key/value heads of 64 (the kernels' first 64-wide shape:
    # q, k, dq pad to the lanes' 128 in VMEM), k and v repeated over their
    # group of 4 in front of the kernels, per-head q/k norms, a plain
    # rotation at 1e6, one sequence of 16,384 steps in bfloat16
    from deeplearning4j_tpu.nn.conf.attention import RotaryAttention
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.perf.pallas import attention
    layer = RotaryAttention(n_heads=32, n_kv_heads=8, head_dim=64,
                            rope_theta=1e6, qk_norm=True, eps=1e-5)
    q = S((1, 32, 16384, 64), BF16)
    assert pk.take("blocked_attention", attention.supported(q, q, q, 512,
                                                            None))
    shapes = jax.eval_shape(lambda k: layer.init(
        k, InputType.recurrent(2048, 16384), BF16)[0], jax.random.key(0))
    params = {k: S(a.shape, BF16) for k, a in shapes.items()}

    def fwd_bwd(params, x):
        return jax.grad(lambda p, x: jnp.sum(layer.apply(
            p, {}, x)[0].astype(F32)), argnums=(0, 1))(params, x)

    return (fwd_bwd, (params, S((1, 16384, 2048), BF16)),
            ("mla_attend_fwd", "mla_attend_bwd"))


def _blocked_attention_64_wide_nope(S):
    # the one attention layer of the Granite 4.0-H stage, through the layer:
    # 32 query heads over 8 key/value heads of 64 with NO rotation and the
    # model's softmax scale (1/64) folded into q in front of the same
    # kernels, one sequence of 8,192 steps in bfloat16
    from deeplearning4j_tpu.nn.conf.attention import RotaryAttention
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.perf.pallas import attention
    layer = RotaryAttention(n_heads=32, n_kv_heads=8, head_dim=64,
                            position_embedding="nope", softmax_scale=1 / 64)
    q = S((1, 32, 8192, 64), BF16)
    assert pk.take("blocked_attention", attention.supported(q, q, q, 512,
                                                            None))
    shapes = jax.eval_shape(lambda k: layer.init(
        k, InputType.recurrent(2048, 8192), BF16)[0], jax.random.key(0))
    params = {k: S(a.shape, BF16) for k, a in shapes.items()}

    def fwd_bwd(params, x):
        return jax.grad(lambda p, x: jnp.sum(layer.apply(
            p, {}, x)[0].astype(F32)), argnums=(0, 1))(params, x)

    return (fwd_bwd, (params, S((1, 8192, 2048), BF16)),
            ("mla_attend_fwd", "mla_attend_bwd"))


def _differential_attention(S, window):
    # one differential attention layer of the Phi-4-mini-flash stage,
    # through the layer: 40 query "heads" of 64 against their k1 / k2 head
    # and the group's 128-wide value, ONE call of shape (1, 40, 8192, 64 |
    # 64 | 128): the kernels' first (64, 128) width pair and, with the
    # window of 512, the band's first window of one tile
    from deeplearning4j_tpu.nn.conf.attention import DifferentialAttention
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.perf.pallas import attention
    layer = DifferentialAttention(n_heads=40, n_kv_heads=20, head_dim=64,
                                  layer_index=15, window=window)
    q, v = S((1, 40, 8192, 64), BF16), S((1, 40, 8192, 128), BF16)
    assert pk.take("blocked_attention", attention.supported(
        q, q, v, 512, window or None))
    shapes = jax.eval_shape(lambda k: layer.init(
        k, InputType.recurrent(2560, 8192), BF16)[0], jax.random.key(0))
    params = {k: S(a.shape, BF16) for k, a in shapes.items()}

    def fwd_bwd(params, x):
        return jax.grad(lambda p, x: jnp.sum(layer.apply(
            p, {}, x)[0].astype(F32)), argnums=(0, 1))(params, x)

    return (fwd_bwd, (params, S((1, 8192, 2560), BF16)),
            ("mla_attend_fwd", "mla_attend_bwd"))


def _differential_attention_window_512(S):
    return _differential_attention(S, 512)


def _differential_attention_causal(S):
    return _differential_attention(S, 0)


def _grouped_experts_1792(S):
    # the routed layer's grouped products and their backward pass at the
    # LFM2 share's widths: 16,384 tokens x top-4 of 32, a quarter held here:
    # a window of 32,768 sorted slots of which some 16,384 are rows of the 8
    # experts of 2048 x 1792 (2,048 rows an expert; 1792 = 14 x 128). The
    # tiles it holds (``_gmm_tiling_dense``): (512, 1024, 896) for gate / up,
    # their ``tgmm`` (11.3 MB of the 16 MB of VMEM) and down's dX, (512, 896,
    # 512) for down, its ``tgmm`` and gate / up's dX. A cap that took the
    # contraction of 2048 whole is refused HERE: the ``tgmm`` at 2048 x 896
    # asks for 20.7 MB (PERF.md section 6, PR 44)
    fwd_bwd = _grouped_experts_16(S)[0]
    return fwd_bwd, (S((32768, 2048), BF16), S((8, 2048, 1792), BF16),
                     S((8, 1792, 2048), BF16), S((8,), I32))


def _inputs_case(S, dtype, gated_delta_net):
    # the delta-rule layers' input path at the two token cells' shapes, one
    # sequence of 8192 steps: KDA's four streams of 32 heads of 128 (q, k,
    # v under their taps, the decay), or Gated DeltaNet's 16 + 16 + 32
    # heads as three column ranges of one product under one taps array,
    # each q/k head written to two value heads; forward and backward
    from deeplearning4j_tpu.perf.pallas import kda_inputs
    if gated_delta_net:
        spec = kda_inputs.Spec(srcs=((0, 0, 0), (0, 0, 16), (0, 0, 32)),
                               decay=None, key_heads=16, rep=2, head_dim=128)
        args = ((S((1, 8192, 96 * 128), dtype),), (S((4, 64 * 128), F32),),
                ())
    else:
        spec = kda_inputs.Spec(srcs=((0, 0, 0), (1, 1, 0), (2, 2, 0)),
                               decay=(3, 0), key_heads=32, rep=1,
                               head_dim=128)
        args = ((S((1, 8192, 4096), dtype),) * 4, (S((4, 4096), F32),) * 3,
                (S((1, 4096), F32),) * 2)
    assert pk.take("kda_inputs", kda_inputs.supported(dtype, 1, 8192, spec,
                                                      4))

    def fwd_bwd(xs, ws, rows):
        def total(xs, ws, rows):
            return sum(jnp.sum(o.astype(F32))
                       for o in kda_inputs.kda_inputs(xs, ws, rows, spec))
        return (kda_inputs.kda_inputs(xs, ws, rows, spec),
                jax.grad(total, argnums=(0, 1, 2))(xs, ws, rows))

    return fwd_bwd, args, ("kda_inputs_fwd", "kda_inputs_bwd")


def _kda_inputs(S):
    return _inputs_case(S, BF16, False)


def _kda_inputs_float32(S):
    return _inputs_case(S, F32, False)


def _gdn_inputs(S):
    return _inputs_case(S, BF16, True)


def _gdn_inputs_float32(S):
    return _inputs_case(S, F32, True)


def _ssd_scan(S):
    # the Mamba-2 scan at the Granite 4.0-H stage's shape, one sequence of
    # 8192 steps, 64 heads of 64 over one 128-wide B and C, chunks of 256:
    # the forward kernel that saves the chunks' entry states and the
    # backward kernel, through chunked_ssd's own selection
    from deeplearning4j_tpu.nn.conf.state_space import chunked_ssd
    from deeplearning4j_tpu.perf.pallas import ssd
    args = (S((1, 8192, 64, 64), BF16), S((1, 8192, 64), F32), S((64,), F32),
            S((1, 8192, 1, 128), BF16), S((1, 8192, 1, 128), BF16))
    assert pk.take("ssd_scan", ssd.supported(*args, 256))

    def fwd_bwd(*a):
        return jax.grad(lambda *a: jnp.sum(chunked_ssd(*a, chunk=256)),
                        argnums=range(5))(*a)

    return fwd_bwd, args, ("ssd_scan_fwd", "ssd_scan_bwd")


def _ssd_scan_float32(S):
    # the same kernels with every product in float32 (what they compile to
    # under jax.default_matmul_precision("highest")), float32 operands, two
    # sequences, heads that fill a lane tile, a chunk of one factor block
    from deeplearning4j_tpu.nn.conf.state_space import chunked_ssd
    args = (S((2, 1024, 6, 128), F32), S((2, 1024, 6), F32), S((6,), F32),
            S((2, 1024, 1, 128), F32), S((2, 1024, 1, 128), F32))

    def fwd_bwd(*a):
        with jax.default_matmul_precision("highest"):
            return jax.grad(lambda *a: jnp.sum(chunked_ssd(*a, chunk=128)),
                            argnums=range(5))(*a)

    return fwd_bwd, args, ("ssd_scan_fwd", "ssd_scan_bwd")


def _selective_scan_args(S, bsz, steps, channels, dtype):
    return (S((bsz, steps, channels), dtype), S((bsz, steps, channels), F32),
            S((channels, 16), F32), S((bsz, steps, 16), dtype),
            S((bsz, steps, 16), dtype), S((channels,), F32))


def _selective_fwd_bwd(x, dt, a, bm, cm, d):
    from deeplearning4j_tpu.nn.conf.state_space import chunked_selective_scan
    return jax.grad(lambda *v: jnp.sum(chunked_selective_scan(
        *v[:5], chunk=64, skip=v[5])), argnums=range(6))(x, dt, a, bm, cm, d)


def _selective_scan(S):
    # the Mamba-1 scan at the Phi-4-mini-flash stage's shape, one sequence
    # of 8192 steps, 5,120 channels x 16 states, bfloat16 x, B and C and a D
    # term: the forward kernel that saves the blocks' entry states and the
    # backward kernel, through chunked_selective_scan's own selection
    from deeplearning4j_tpu.perf.pallas import selective_scan
    args = _selective_scan_args(S, 1, 8192, 5120, BF16)
    assert pk.take("selective_scan", selective_scan.supported(*args))
    return _selective_fwd_bwd, args, ("selective_scan_fwd",
                                      "selective_scan_bwd")


def _selective_scan_float32(S):
    # the same kernels on float32 operands, two sequences, three tiles of
    # 128 channels (the narrowest the kernels take)
    return (_selective_fwd_bwd, _selective_scan_args(S, 2, 512, 384, F32),
            ("selective_scan_fwd", "selective_scan_bwd"))


def _bn_fwd(S):
    # ResNet50 batch 128, the one stage whose rows fit: 7x7
    z = S((128, 7, 7, 2048), BF16)
    assert bn.supported(z, has_res=True)
    return (lambda z, g, b, r: bn.bn_act_fwd("relu", 1e-5, z, g, b, r)), (
        z, S((2048,), F32), S((2048,), F32), z)


def _bn_bwd(S):
    z, c = S((64, 7, 7, 512), BF16), S((512,), F32)
    assert bn.supported(z, backward=True)
    return (lambda z, g, b, m, i, d: bn.bn_act_bwd(
        "relu", 1e-5, z, g, b, None, m, i, d)), (z, c, c, c, c, z)


# (builder, family the automatic TPU rule must select for it | None)
AUTO_CASES = [(_pq, "adc_pq"), (_int4_table, "int4_dot"),
              (_int4_weights, "int4_dot"), (_flash, None), (_scatter, None),
              (_grouped_experts, None), (_kda_scan, "kda_scan"),
              (_kda_scan_float32, None),
              (_blocked_attention, "blocked_attention"),
              (_blocked_attention_float32, None),
              (_kda_scan_scalar_decay, None),
              (_blocked_attention_grouped, None),
              (_blocked_attention_rotary, None),
              (_blocked_attention_band, None),
              (_blocked_attention_16k, None),
              (_blocked_attention_band_float32, None),
              (_grouped_experts_16, None),
              (_blocked_attention_64_wide, None),
              (_blocked_attention_64_wide_nope, None),
              (_differential_attention_window_512, None),
              (_differential_attention_causal, None),
              (_grouped_experts_1792, None),
              (_kda_inputs, "kda_inputs"), (_kda_inputs_float32, None),
              (_gdn_inputs, None), (_gdn_inputs_float32, None),
              (_ssd_scan, "ssd_scan"), (_ssd_scan_float32, None),
              (_selective_scan, "selective_scan"),
              (_selective_scan_float32, None)]
EXPLICIT_CASES = [_bn_fwd, _bn_bwd]


def _compiles_with_kernel(fn, args, kernels=()):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    for name in kernels:
        assert name in text, f"no kernel named {name} in the program"


@pytest.mark.parametrize("build", [c[0] for c in AUTO_CASES]
                         + EXPLICIT_CASES, ids=lambda f: f.__name__[1:])
def test_v5e_compiler_accepts(build, v5e, tpu_backend):
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    if build in EXPLICIT_CASES:  # outside the automatic rule
        assert not pk.enabled("bn_act") and not pk.enabled("bn_act_bwd")
        with pk.override(enabled=True):
            _compiles_with_kernel(*build(S))
    else:
        _compiles_with_kernel(*build(S))


def test_the_gather_dispatch_reads_its_windows_from_fast_memory(v5e):
    """The routed layers' gather form at the Mellum2 share's shapes (16,384
    tokens x top-8, a window of 65,536 sorted slots of 2304 bfloat16),
    forward and gradient: the window's rows go in three blocks of 768
    columns (96 MiB each, ``GATHER_OPERAND_BYTES``), and the compiler gives
    them the fast memory (``S(1)`` in the operand's layout), where a gather
    of the whole 288 MiB window reads from HBM at a fifth of the pace a row
    (PERF.md section 6, PR 38)."""
    import re

    from deeplearning4j_tpu.nn.conf import experts

    n, k, d, window = 16384, 8, 2304, 65536
    assert experts._gathers(n * k, window)
    assert experts._column_blocks(window, d, 2) == [(0, 768), (768, 1536),
                                                    (1536, 2304)]

    def grads(xf, w, order, n_held):
        rank = experts._inverse(order).reshape(n, k)
        slots = order[:window]

        def loss(xf, w):
            rows = experts._take_rows(xf, slots // k, rank, 0, n_held)
            y = experts._combine(rows * jnp.bfloat16(0.5), w, slots, rank, 0,
                                 n_held)
            return jnp.sum(y ** 2)
        return jax.grad(loss, (0, 1))(xf, w)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    text = jax.jit(grads).lower(S((n, d), BF16), S((n, k), F32),
                                S((n * k,), I32), S((), I32)
                                ).compile().as_text()
    layouts = {m.group(1): m.group(2) for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (\S+)", text, re.M)}
    blocks = [layouts[m.group(1)] for m in re.finditer(
        r"= bf16\[131072,768\]\S* fusion\(%([\w.\-]+),", text)]
    assert len(blocks) == 6, blocks          # combine and take-rows' backward
    assert all(b.startswith("bf16[65536,768]") for b in blocks), blocks
    # the placement is the compiler's own choice, program by program: all
    # six in the Mellum2 cell's step, five of six in this one
    assert sum("S(1)" in b for b in blocks) >= 4, blocks
    assert "scatter" not in text


def test_the_state_space_scan_compiles_for_the_chip(v5e, tpu_backend):
    """``chunked_ssd`` at the Granite 4.0-H stage's shape (64 heads of 64
    over one shared 128-wide B and C, 8,192 steps in chunks of 256,
    bfloat16 operands), forward and every gradient: the forward and the
    backward kernel, no ``while`` over the chunks, and nothing of a chunk's
    (heads, 256, 256) factors in HBM: what the program holds beside its
    operands is y and its cotangent in float32 (134 MB each), the chunks'
    entry states (67 MB) and the backward kernel's vectors."""
    import re

    fwd_bwd, args, kernels = _ssd_scan(
        lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e))
    compiled = jax.jit(fwd_bwd).lower(*args).compile()
    text = compiled.as_text()
    for name in kernels:
        assert re.search(rf'custom_call_target="tpu_custom_call".*{name}',
                         text), name
    assert " while(" not in text
    assert not re.search(r"\[(\d+,)+256,256\]", text)   # no factors in HBM
    assert compiled.memory_analysis().temp_size_in_bytes < 0.45e9


@pytest.mark.parametrize("kind", ["KimiDeltaAttention", "GatedDeltaNet"])
def test_a_rematerialised_delta_rule_layer_runs_each_scan_kernel_once(
        kind, v5e, tpu_backend):
    """A delta-rule layer's gradient program under ``remat="full"``, lowered
    and compiled for the described v5e (32 heads of 128 as in the cells,
    1,024 steps): ONE ``kda_scan_fwd``, in its ``save`` form (o, the chunks'
    entry states, the chunks' solved u, their scores [P | kk_off]), and ONE
    ``kda_scan_bwd`` of nine operands (q, k, v, g, b, the states, u, the
    scores, dO). A residual that was not named would show as a second
    ``kda_scan_fwd`` (the rematerialised first pass)."""
    import re

    from deeplearning4j_tpu.nn.conf import InputType
    from deeplearning4j_tpu.nn.conf import linear_attention as la
    from deeplearning4j_tpu.nn.conf.layers import apply_layer
    layer = (la.KimiDeltaAttention(n_heads=32, head_dim=128, low_rank=128,
                                   remat="full")
             if kind == "KimiDeltaAttention" else
             la.GatedDeltaNet(n_key_heads=16, n_value_heads=32, head_dim=128,
                              remat="full"))
    assert {"kda_scan.u", "kda_scan.scores"} < set(layer.remat_keeps)
    it = InputType.recurrent(2048, 1024)
    params, state = jax.eval_shape(
        lambda: layer.init(jax.random.key(0), it))

    def S(a, dtype=BF16):
        return jax.ShapeDtypeStruct(a.shape, dtype, sharding=v5e)

    def loss(p, x):
        out, _ = apply_layer(layer, p, state, x, train=True, rng=None,
                             mask=None, name="mix")
        return jnp.sum(out.astype(F32))

    lowered = jax.jit(jax.grad(loss)).lower(
        jax.tree.map(S, params), S(jax.ShapeDtypeStruct((1, 1024, 2048),
                                                        BF16)))
    text = lowered.as_text()
    for kernel in ("kda_scan_fwd", "kda_scan_bwd"):
        assert len(re.findall(rf'kernel_name = "{kernel}"', text)) == 1, kernel
    compiled = lowered.compile().as_text()
    wide, states = "f32[1,32,1024,128]", "f32[1,32,16,128,128]"
    calls = {kernel: line.split(" custom-call(")
             for line in compiled.splitlines()
             for kernel in ("kda_scan_fwd", "kda_scan_bwd")
             if "tpu_custom_call" in line and f"%{kernel}" in line}
    results, _ = calls["kda_scan_fwd"]
    assert re.findall(r"\w+\[[\d,]+\]", results) == [wide, states, wide,
                                                         wide]
    _, operands = calls["kda_scan_bwd"]
    operands = operands.split("), custom_call_target")[0]
    assert operands.count("%") == 9      # q k v g b states u scores dO


def test_the_selective_scan_compiles_for_the_chip(v5e, tpu_backend):
    """``chunked_selective_scan`` at the Phi-4-mini-flash stage's shape
    (5,120 channels x 16 states, 8,192 steps, bfloat16 x, B and C), forward
    and all six gradients: the forward and the backward kernel, no ``while``
    over chunks or steps, and no states in HBM but the 32 blocks' entry
    states (10.5 MB): never a (.., 64, 16, 5120) chunk of them nor a
    (time, 16, 5120) array. What the program holds at once beside its
    operands and results is y's cotangent (168 MB), B and C, then dB and dC,
    with every value over a lane tile (2 x 67 MB) and the entry states:
    0.38 GB, held under 0.45 where XLA's loops were held under 0.9."""
    import re

    fwd_bwd, args, kernels = _selective_scan(
        lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e))
    compiled = jax.jit(fwd_bwd).lower(*args).compile()
    text = compiled.as_text()
    for name in kernels:
        assert re.search(rf'custom_call_target="tpu_custom_call".*{name}',
                         text), name
    assert " while(" not in text
    assert not re.search(r"\[(\d+,)*64,16,5120\]", text)
    assert not re.search(r"\[(\d+,)*8192,16,5120\]", text)
    assert "f32[1,32,16,5120]" in text          # the blocks' entry states
    assert compiled.memory_analysis().temp_size_in_bytes < 0.45e9


def test_every_auto_family_has_a_case(tpu_backend):
    selected = {f for f, impl in pk.selection_snapshot().items()
                if impl == "pallas"}
    assert selected == set(pk.TPU_AUTO_FAMILIES)
    assert selected == {fam for _, fam in AUTO_CASES if fam}
    # a family with no compilable kernel stays off a TPU even when a
    # TuningRecord force-enables the layer process-wide
    with pk.override(enabled=True):
        assert pk.enabled("bn_act") and not pk.enabled("adc_ivf_pq")
        with pk.override(interpret=True):
            assert pk.enabled("adc_ivf_pq")


def test_bn_supported_refuses_what_cannot_fit():
    # ResNet50 batch 128 stage 2: "input window allocation ...
    # bf16[401408,128]", 205 MB against 128 MiB of VMEM
    z = jax.ShapeDtypeStruct((128, 56, 56, 256), BF16)
    assert not bn.supported(z)
    assert not bn.supported(z, backward=True)
    with pk.override(enabled=True):
        assert not pk.take("bn_act", bn.supported(z))
