"""Local-attention backend dispatch (blendjax.ops.attention).

The fused kernel runs here in Pallas interpreter mode, so the whole
flash path — mask, kernel forward and backward, and the pad and slice
of the lengths no block divides — is compared
with ``reference_attention`` on the CPU; its speed and the chip's
compiler are tests/test_tpu_compile.py's and chip_smoke.py's.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from blendjax.ops import attention as A  # noqa: E402
from blendjax.ops.attention import (  # noqa: E402
    FLASH_MAX_KV,
    FLASH_RESIDUAL_BYTES,
    FLASH_TILE_ELEMS,
    auto_picks_flash,
    batch_sharded_over,
    flash_block_sizes,
    flash_supported,
    local_attention,
    local_attention_packed,
    scores_residual_bytes,
)
from blendjax.parallel.ring import reference_attention  # noqa: E402
from blendjax.utils.metrics import (  # noqa: E402
    RESIDUAL_ATTN_LSE,
    RESIDUAL_ATTN_O,
    metrics,
)


def _qkv(t=128, b=2, h=2, d=64, dtype=jnp.float32, t_kv=None):
    k = jax.random.key(0)
    return tuple(
        jax.random.normal(
            jax.random.fold_in(k, i),
            (b, t if i == 0 else (t_kv or t), h, d), dtype,
        )
        for i in range(3)
    )


class _Shape:
    """An input as the policy sees it: a shape."""

    def __init__(self, *shape):
        self.shape, self.ndim = shape, len(shape)


@pytest.fixture
def one_tpu(monkeypatch):
    """What the policy asks of the process: a TPU backend, one device."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)


def _attn_counts():
    return {
        k: v for k, v in metrics.report()["counters"].items()
        if k.startswith("attn.path.")
    }


def test_auto_stays_on_xla_off_tpu():
    q, _, _ = _qkv()
    if jax.default_backend() != "tpu":
        assert flash_supported(q)  # the shape is eligible; the backend is not
        assert not auto_picks_flash(q)
        assert not auto_picks_flash(_Shape(8, 1200, 12, 64))


def test_explicit_flash_raises_when_unsupported():
    """Same contract as the tile decode's use_pallas: an explicit
    backend request must fail loudly, never silently measure xla."""
    for bad in (dict(d=48), dict(h=3, d=64), dict(d=256)):
        q, k, v = _qkv(**bad)
        assert not flash_supported(q, k)
        with pytest.raises(ValueError, match="flash attention backend"):
            local_attention(q, k, v, backend="flash")


def test_unknown_backend_rejected():
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="unknown attention backend"):
        local_attention(q, k, v, backend="turbo")


def test_flash_support_checks_kv_length_too():
    """Any length is padded and masked, the KV side of cross-attention
    too; what the kernel cannot take is more keys than one head keeps
    in VMEM."""
    assert flash_supported(_Shape(1, 128, 2, 64), _Shape(1, 120, 2, 64))
    assert flash_supported(_Shape(1, 128, 2, 64), _Shape(1, FLASH_MAX_KV, 2, 64))
    assert not flash_supported(
        _Shape(1, 128, 2, 64), _Shape(1, FLASH_MAX_KV + 1, 2, 64)
    )
    assert not flash_supported(_Shape(128, 2, 64))


def _aligned_divisor(t_q, tile):
    return any(t_q % b == 0 for b in range(tile, t_q + 1, tile))


@pytest.mark.parametrize(
    "t_q, t_kv, dtype",
    [(64, 64, jnp.bfloat16), (130, 130, jnp.bfloat16),
     (197, 197, jnp.bfloat16), (1200, 1200, jnp.bfloat16),
     (3072, 3072, jnp.bfloat16), (256, 1000, jnp.bfloat16),
     (5000, 16384, jnp.bfloat16), (1200, 1200, jnp.float32),
     (400, 1200, jnp.bfloat16)],
)
def test_flash_block_sizes_from_the_shape(t_q, t_kv, dtype):
    """Eligibility and launch share one source of truth, computed from
    the shape: whole sublane tiles a block, blocks that tile the rows Q
    has in HBM — ``t_q`` itself, unpadded, wherever it has an aligned
    divisor worth taking; elsewhere 128-row blocks and less than one of
    padding — one lane tile of key padding at most, and a score tile
    within FLASH_TILE_ELEMS wherever a 128-row block allows."""
    tile = A._sublanes(dtype)
    block_q, padded_q, padded_kv = flash_block_sizes(t_q, t_kv, dtype)
    assert block_q % tile == 0 and padded_kv % 128 == 0
    assert padded_q % block_q == 0
    assert t_q <= padded_q < t_q + block_q
    assert t_kv <= padded_kv < t_kv + 128
    assert block_q <= 128 or block_q * padded_kv <= FLASH_TILE_ELEMS
    if padded_q != t_q:
        assert block_q % 128 == 0
    if _aligned_divisor(t_q, tile) and t_q * padded_kv <= FLASH_TILE_ELEMS:
        assert (block_q, padded_q) == (t_q, t_q)
    # the seven shapes of PR 26 with their geometry, by name
    assert (padded_q == t_q) == (t_q not in (130, 197, 5000))


def test_flash_block_sizes_at_the_benchmark_shape():
    """1,200 tokens are one block of 1,200 rows against 1,280 key lanes;
    bf16 blocks are whole 16-row tiles, so 600 rows pad where f32's
    8-row tiles do not; 768 keeps PR 26's geometry and 3,072 takes the
    512 rows the raised bound admits."""
    assert flash_block_sizes(1200, 1200) == (1200, 1200, 1280)
    assert flash_block_sizes(1200, 1200, jnp.float32) == (1200, 1200, 1280)
    assert flash_block_sizes(400, 1200) == (400, 400, 1280)
    assert flash_block_sizes(600, 1200) == (640, 640, 1280)
    assert flash_block_sizes(600, 1200, jnp.float32) == (600, 600, 1280)
    assert flash_block_sizes(2400, 2400) == (800, 2400, 2432)
    assert flash_block_sizes(3072, 3072) == (512, 3072, 3072)
    assert flash_block_sizes(768, 768) == (768, 768, 768)
    assert flash_block_sizes(197, 197) == (256, 256, 256)


@pytest.mark.parametrize(
    "shape, flash",
    [((8, 197, 12, 64), False), ((8, 768, 4, 128), True),
     ((8, 1200, 12, 64), True), ((4, 3072, 4, 128), True),
     ((8, 256, 12, 64), True), ((8, 512, 4, 128), True),
     ((8, 384, 12, 64), True), ((8, 64, 4, 128), False)],
)
def test_scores_residual_bytes_and_auto_threshold(one_tpu, shape, flash):
    """The auto policy at the four shapes ISSUE 26 named, three of the
    four measured between them (module docstring) and the rehearsal's
    64 tokens: bytes of f32 scores a call against
    FLASH_RESIDUAL_BYTES, on a TPU."""
    b, t, h, _ = shape
    assert scores_residual_bytes(_Shape(*shape)) == b * h * t * t * 4
    assert (
        scores_residual_bytes(_Shape(*shape)) >= FLASH_RESIDUAL_BYTES
    ) == flash
    assert auto_picks_flash(_Shape(*shape)) == flash


@pytest.mark.parametrize("backend", ["auto", "xla"])
def test_dispatch_matches_reference_off_tpu(backend):
    """Off-TPU, auto resolves to the xla path — and says so."""
    q, k, v = _qkv()
    before = _attn_counts().get("attn.path.xla", 0)
    out = local_attention(q, k, v, backend=backend)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(reference_attention(q, k, v)),
        atol=1e-6,
    )
    assert _attn_counts()["attn.path.xla"] == before + 1


def _loss_and_grads(fn, q, k, v, w):
    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    return jax.value_and_grad(loss, (0, 1, 2))(q, k, v)


@pytest.mark.parametrize(
    "t, t_kv, causal",
    [(130, None, False), (197, None, False), (1200, None, False),
     (130, None, True), (64, 300, False), (128, None, False)],
    ids=["T130", "T197", "T1200", "T130-causal", "cross-64x300",
         "T128-unpadded"],
)
def test_flash_pad_and_mask_matches_reference(t, t_kv, causal):
    """The pad-and-mask path equals reference_attention, output and the
    three gradients, at lengths no block tiles."""
    b = 1 if t > 1000 else 2
    q, k, v = _qkv(t=t, t_kv=t_kv, b=b)
    w = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)
    before = _attn_counts().get("attn.path.flash", 0)
    loss, grads = _loss_and_grads(
        lambda *a: local_attention(*a, causal=causal, backend="flash"),
        q, k, v, w,
    )
    assert _attn_counts()["attn.path.flash"] == before + 1
    want, want_grads = _loss_and_grads(
        lambda *a: reference_attention(*a, causal=causal), q, k, v, w
    )
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for got, ref in zip(grads, want_grads):
        assert got.shape == ref.shape
        np.testing.assert_allclose(got, ref, atol=5e-6)


def _primitives_around_the_core(jaxpr):
    """Names of what a traced flash call does outside the kernels'
    ``custom_vjp_call``, through the ``jit`` of ``jnp.pad``."""
    names = set()
    for eqn in jaxpr.eqns:
        inner = eqn.params.get("jaxpr")
        if eqn.primitive.name == "jit" and inner is not None:
            names |= _primitives_around_the_core(inner.jaxpr)
        else:
            names.add(eqn.primitive.name)
    return names


@pytest.mark.parametrize(
    "t, t_kv, path",
    [(1200, None, "exact"), (197, None, "padded"), (400, 1200, "exact"),
     (64, 300, "exact"), (130, 256, "padded")],
    ids=["T1200", "T197", "cross-400x1200", "cross-64x300", "cross-130x256"],
)
def test_flash_path_counter_says_whether_hbm_holds_padding(t, t_kv, path):
    """``attn.path.flash_exact`` where every operand enters the kernel
    at its own length (K/V always do: 300 keys are padded in VMEM),
    ``flash_padded`` where no block divides ``t_q`` and Q is padded in
    HBM — and no ``pad`` and no ``slice`` in the traced program in the
    first case."""
    q, k, v = _qkv(t=t, t_kv=t_kv, b=1)
    before = _attn_counts()
    jaxpr = jax.make_jaxpr(
        lambda *a: local_attention(*a, backend="flash")
    )(q, k, v)
    after = _attn_counts()
    other = {"exact": "padded", "padded": "exact"}[path]
    moved = {
        name: after.get(f"attn.path.{name}", 0)
        - before.get(f"attn.path.{name}", 0)
        for name in ("flash", f"flash_{path}", f"flash_{other}")
    }
    assert moved == {"flash": 1, f"flash_{path}": 1, f"flash_{other}": 0}
    outside = _primitives_around_the_core(jaxpr.jaxpr)
    assert ("pad" in outside) == (path == "padded"), outside
    assert ("slice" in outside) == (path == "padded"), outside


@pytest.mark.parametrize(
    "t, t_kv, dtype",
    [(1200, None, jnp.float32), (400, 1200, jnp.float32),
     (1200, None, jnp.bfloat16)],
    ids=["T1200", "cross-400x1200", "T1200-bf16"],
)
def test_flash_exact_length_gradients_match_reference(t, t_kv, dtype):
    """An exact-length call (no operand padded in HBM, K/V padded in
    VMEM): the gradients have the inputs' shapes and dtypes and equal
    ``reference_attention``'s."""
    q, k, v = _qkv(t=t, t_kv=t_kv, dtype=dtype)
    assert flash_block_sizes(t, t_kv or t, dtype).padded_q == t
    w = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)
    loss, grads = _loss_and_grads(
        lambda *a: local_attention(*a, backend="flash"), q, k, v, w
    )
    want, want_grads = _loss_and_grads(reference_attention, q, k, v, w)
    f32 = dtype == jnp.float32
    np.testing.assert_allclose(loss, want, rtol=1e-5 if f32 else 2e-2)
    for got, ref, x in zip(grads, want_grads, (q, k, v)):
        assert got.shape == x.shape and got.dtype == x.dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=5e-6 if f32 else 2e-2,
        )


@pytest.mark.parametrize("h, d", [(1, 128), (4, 32)], ids=["D128", "D32"])
def test_flash_head_widths_that_fill_the_lanes(h, d):
    """One 128-wide head a block, and four 32-wide ones."""
    q, k, v = _qkv(t=130, b=1, h=h, d=d)
    w = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)
    loss, grads = _loss_and_grads(
        lambda *a: local_attention(*a, backend="flash"), q, k, v, w
    )
    want, want_grads = _loss_and_grads(reference_attention, q, k, v, w)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for got, ref in zip(grads, want_grads):
        np.testing.assert_allclose(got, ref, atol=5e-6)


def test_flash_bf16_keeps_the_reference_precision():
    """bf16 operands, f32 statistics: against the f32 reference the
    kernel is no further off than the materialised bf16 path."""
    q, k, v = _qkv(t=200, dtype=jnp.bfloat16)
    exact = reference_attention(*(x.astype(jnp.float32) for x in (q, k, v)))
    err = {
        backend: float(jnp.max(jnp.abs(
            local_attention(q, k, v, backend=backend).astype(jnp.float32)
            - exact
        )))
        for backend in ("flash", "xla")
    }
    assert err["flash"] <= 2 * err["xla"], err


def test_flash_runs_per_batch_shard_under_a_declared_mesh():
    """A mesh declared while the model is traced wraps the kernel in
    shard_map over the batch axis: same numbers, batch-sharded
    gradients, no all-gather of q/k/v in the compiled program."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    by_batch = NamedSharding(mesh, P("data"))
    q, k, v = (jax.device_put(x, by_batch) for x in _qkv(t=130, b=4))
    w = jnp.ones(q.shape, jnp.float32)

    def run(backend):
        def fn(q, k, v):
            with batch_sharded_over(mesh, "data"):
                return local_attention(q, k, v, backend=backend)

        return jax.jit(lambda *a: _loss_and_grads(fn, *a, w))

    before = _attn_counts().get("attn.path.shard_map", 0)
    loss, grads = run("flash")(q, k, v)
    assert _attn_counts()["attn.path.shard_map"] == before + 1
    want, want_grads = run("xla")(q, k, v)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for got, ref in zip(grads, want_grads):
        np.testing.assert_allclose(got, ref, atol=5e-6)
        assert got.sharding.spec == P("data")
    assert "all-gather" not in run("flash").lower(q, k, v).compile().as_text()


def test_auto_takes_the_kernel_only_where_it_knows_the_program(monkeypatch):
    """One device: bare. A declared mesh: per shard, if its batch axis
    divides the batch. Several devices and nothing declared (a jit with
    shardings, a model's init on a mesh): the lowering would refuse a
    bare kernel, so auto keeps XLA."""
    from jax.sharding import Mesh

    shape = _Shape(8, 1200, 12, 64)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert jax.device_count() > 1 and not auto_picks_flash(shape)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    with batch_sharded_over(mesh, "data"):
        assert auto_picks_flash(shape)
        assert not auto_picks_flash(_Shape(6, 1200, 12, 64))
    with batch_sharded_over(Mesh(np.array(jax.devices()[:1]), ("data",))):
        assert auto_picks_flash(_Shape(6, 1200, 12, 64))
    assert A._PROGRAM_MESH.get() is None
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert auto_picks_flash(shape)


def _counted(before, *names):
    """How far each ``attn.path.<name>`` counter moved since ``before``."""
    after = _attn_counts()
    return {
        name: after.get(f"attn.path.{name}", 0)
        - before.get(f"attn.path.{name}", 0)
        for name in names
    }


def _packed(t, h, d, dtype, b=2):
    """One projection's result as the packed entry takes it:
    ``(B, T, 3·H·D)``, columns ``[q | k | v][head][d]``."""
    return jax.random.normal(jax.random.key(4), (b, t, 3 * h * d), dtype)


def _split(qkv, h):
    b, t, w = qkv.shape
    return tuple(x.reshape(b, t, h, w // (3 * h))
                 for x in jnp.split(qkv, 3, axis=2))


def _packed_value_and_grad(fn, qkv, w):
    """``fn(qkv)`` in float32 and the gradient of its ``w``-weighted
    sum with respect to the packed array."""
    def loss(a):
        out = fn(a).astype(jnp.float32)
        return jnp.sum(out * w), out

    (_, out), grad = jax.value_and_grad(loss, has_aux=True)(qkv)
    return out, grad


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("blocks", [1, 2], ids=["one-block", "two-blocks"])
@pytest.mark.parametrize("h, d", [(2, 64), (2, 128)], ids=["D64", "D128"])
def test_packed_entry_matches_reference(monkeypatch, h, d, blocks, causal,
                                        dtype):
    """``local_attention_packed`` reads q, k, v out of the one packed
    array and writes one packed gradient: value and the gradient with
    respect to the packed array against ``reference_attention`` on the
    three slices, for two heads a lane block and one, one query block
    and several (the gradient's dq rows land a block at a time, dk and
    dv at the last), causal or not, f32 and bf16."""
    t = 256
    if blocks > 1:  # a 128-row block: the bound admits no larger tile
        monkeypatch.setattr(A, "FLASH_TILE_ELEMS", 128 * t)
    assert flash_block_sizes(t, t, dtype).block_q == t // blocks
    qkv = _packed(t, h, d, dtype)
    w = jax.random.normal(jax.random.key(9), (2, t, h, d), jnp.float32)
    before = _attn_counts()
    out, grad = _packed_value_and_grad(
        lambda a: local_attention_packed(a, h, causal=causal,
                                         backend="flash"), qkv, w)
    assert _counted(
        before, "flash", "flash_exact", "flash_packed", "flash_padded"
    ) == {"flash": 1, "flash_exact": 1, "flash_packed": 1, "flash_padded": 0}
    want, want_grad = _packed_value_and_grad(
        lambda a: reference_attention(*_split(a, h), causal=causal), qkv, w)
    f32 = dtype == jnp.float32
    assert out.shape == (2, t, h, d)
    np.testing.assert_allclose(out, want, atol=5e-6 if f32 else 3e-2)
    assert grad.shape == qkv.shape and grad.dtype == qkv.dtype
    np.testing.assert_allclose(
        np.asarray(grad, np.float32), np.asarray(want_grad, np.float32),
        atol=5e-6 if f32 else 3e-2,
    )


@pytest.mark.parametrize("t, backend, path", [
    (197, "flash", "flash_padded"), (130, "flash", "flash_padded"),
    (128, "xla", "xla"),
])
def test_packed_entry_elsewhere_takes_the_three_tensor_path(t, backend,
                                                            path):
    """A length no query block divides (Q padded in HBM) and the XLA
    backend have nothing to gain from the packed array: the entry
    slices it and calls ``local_attention`` — the same numbers, counted
    as that path and not as ``flash_packed``."""
    h, d = 2, 64
    qkv = _packed(t, h, d, jnp.float32, b=1)
    w = jax.random.normal(jax.random.key(9), (1, t, h, d), jnp.float32)
    before = _attn_counts()
    out, grad = _packed_value_and_grad(
        lambda a: local_attention_packed(a, h, backend=backend), qkv, w)
    assert _counted(before, path, "flash_packed") == {
        path: 1, "flash_packed": 0}
    want, want_grad = _packed_value_and_grad(
        lambda a: reference_attention(*_split(a, h)), qkv, w)
    np.testing.assert_allclose(out, want, atol=5e-6)
    np.testing.assert_allclose(grad, want_grad, atol=5e-6)


def test_packed_entry_runs_per_batch_shard_under_a_declared_mesh():
    """The packed call goes through ``shard_map`` over the batch axis
    as the three-tensor call does: the packed gradient stays sharded by
    batch, and the replicated bias's gradient, which each shard's
    kernel sums over its own rows, is summed over the shards."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    h, d, t = 2, 64, 128
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    qkv = jax.device_put(_packed(t, h, d, jnp.float32, b=4),
                         NamedSharding(mesh, P("data")))
    bias = jax.device_put(
        jax.random.normal(jax.random.key(6), (3 * h * d,), jnp.float32),
        NamedSharding(mesh, P()),
    )
    w = jax.random.normal(jax.random.key(9), (4, t, h, d), jnp.float32)

    def run(backend):
        def loss(qkv, bias):
            with batch_sharded_over(mesh, "data"):
                out = local_attention_packed(qkv, h, bias=bias,
                                             backend=backend)
            return jnp.sum(out * w)

        return jax.jit(jax.value_and_grad(loss, (0, 1)))

    before = _attn_counts()
    loss, (dqkv, dbias) = run("flash")(qkv, bias)
    assert _counted(before, "flash_packed", "shard_map") == {
        "flash_packed": 1, "shard_map": 1}
    want, (want_dqkv, want_dbias) = run("xla")(qkv, bias)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    np.testing.assert_allclose(dqkv, want_dqkv, atol=5e-6)
    np.testing.assert_allclose(dbias, want_dbias, atol=5e-5)
    assert dqkv.sharding.spec == P("data")
    assert "all-gather" not in run("flash").lower(qkv, bias).compile().as_text()


def test_packed_entry_refuses_what_the_kernel_cannot_take():
    """An explicit ``flash`` on heads that do not fill the lanes fails
    as loudly through the packed entry as through the three tensors."""
    with pytest.raises(ValueError, match="flash attention backend"):
        local_attention_packed(_packed(128, 3, 64, jnp.float32), 3,
                               backend="flash")


@pytest.mark.parametrize("t, causal, packed", [
    (128, False, 1), (128, True, 1), (130, False, 0),
], ids=["T128", "T128-causal", "T130-padded"])
def test_multi_head_attention_flash_equals_xla(t, causal, packed):
    """The module on the same parameters under ``attn_backend="flash"``
    (the flat ``qkv`` product into the packed kernels where a query
    block divides the tokens; ``DenseGeneral`` and three tensors where
    none does) and ``"xla"``: the output and every parameter's
    gradient."""
    from blendjax.models.transformer import MultiHeadAttention

    x = jax.random.normal(jax.random.key(1), (2, t, 128), jnp.float32)
    w = jax.random.normal(jax.random.key(2), x.shape, jnp.float32)
    modules = {
        backend: MultiHeadAttention(2, dtype=jnp.float32, causal=causal,
                                    attn_backend=backend)
        for backend in ("xla", "flash")
    }
    params = modules["xla"].init(jax.random.key(0), x)["params"]
    params["qkv"]["bias"] = 0.1 * jax.random.normal(
        jax.random.key(3), params["qkv"]["bias"].shape, jnp.float32
    )

    def value_and_grads(backend):
        def loss(p):
            y = modules[backend].apply({"params": p}, x)
            return jnp.sum(y * w), y

        (_, y), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return y, grads

    before = _attn_counts()
    got, got_grads = value_and_grads("flash")
    assert _counted(before, "flash_packed") == {"flash_packed": packed}
    want, want_grads = value_and_grads("xla")
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert (jax.tree_util.tree_structure(got_grads)
            == jax.tree_util.tree_structure(want_grads))
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(got_grads),
        jax.tree_util.tree_leaves(want_grads),
    ):
        assert a.shape == b.shape and a.dtype == b.dtype, path
        np.testing.assert_allclose(
            a, b, atol=2e-5 * float(jnp.abs(b).max()), err_msg=str(path)
        )


@pytest.mark.parametrize("side, tokens, packed", [
    ((480, 640), 1200, 12), ((224, 224), 196, 0),
], ids=["vit_b16-1200", "vit_b16-224px"])
def test_vit_b16_counts_one_packed_call_a_block(one_tpu, side, tokens,
                                                packed):
    """Traced (``eval_shape``: nothing runs) as a one-chip TPU process
    would: ``vit_b16`` at the stream's 1,200 tokens takes the packed
    kernels in each of its 12 blocks, at 224 px none (the XLA side of
    ``auto``). No field chooses: the shape does."""
    from blendjax.models import StreamFormer

    model = StreamFormer(patch=16, dim=768, depth=12, num_heads=12,
                         num_outputs=16)
    images = jax.ShapeDtypeStruct((8, *side, 4), jnp.uint8)
    params = jax.eval_shape(model.init, jax.random.key(0), images)
    before = _attn_counts()
    jax.eval_shape(model.apply, params, images)
    assert _counted(before, "flash_packed", "flash", "xla") == {
        "flash_packed": packed, "flash": packed, "xla": 12 - packed}
    assert tokens == (side[0] // 16) * (side[1] // 16)


@pytest.mark.parametrize("c, h", [(128, 2), (768, 12)])
def test_qkv_parameters_are_dense_generals(c, h):
    """``qkv/kernel`` ``(c, 3, h, d)`` and ``qkv/bias`` ``(3, h, d)``,
    float32, drawn as ``nn.DenseGeneral`` draws them (the flattened
    ``(c, 3·h·d)`` shape, reshaped): the packed path's module and the
    other one give the same tree from the same key, to the last bit, so
    a seeded run starts from the same numbers whichever path its shape
    takes (``benchmark/reference.py``'s checksum depends on it)."""
    import flax.linen as nn

    from blendjax.models.transformer import PackedQKV

    x = jnp.zeros((1, 16, c), jnp.float32)
    old = nn.DenseGeneral(
        (3, h, c // h), axis=-1, dtype=jnp.bfloat16, param_dtype=jnp.float32
    ).init(jax.random.key(7), x)
    new = PackedQKV(h, dtype=jnp.bfloat16).init(jax.random.key(7), x)
    assert jax.tree_util.tree_structure(old) == jax.tree_util.tree_structure(new)
    assert new["params"]["kernel"].shape == (c, 3, h, c // h)
    assert new["params"]["bias"].shape == (3, h, c // h)
    for a, b in zip(jax.tree_util.tree_leaves(old),
                    jax.tree_util.tree_leaves(new)):
        assert a.dtype == b.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_state_saved_with_dense_general_restores_into_the_packed_path(
    tmp_path,
):
    """``tests/fixtures/streamformer_dense_qkv_snapshot`` was written by
    the tree before this change (PR 34's, ``nn.DenseGeneral`` and three
    tensors: the state after one sgd update, the frames it saw and what
    it answered). It restores leaf for leaf into today's model, which
    answers the same through the packed kernels."""
    import os
    import shutil

    import optax

    from blendjax.checkpoint import SnapshotManager
    from blendjax.models import StreamFormer
    from blendjax.train import make_train_state

    fixture = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "fixtures",
        "streamformer_dense_qkv_snapshot",
    )
    directory = tmp_path / "snapshot"
    shutil.copytree(fixture, directory)  # the manager sweeps what it opens
    model = StreamFormer(patch=8, dim=128, depth=1, num_heads=2,
                         num_outputs=16, dtype=jnp.float32,
                         attn_backend="flash")
    template = make_train_state(
        model, np.zeros((2, 32, 32, 4), np.uint8),
        optimizer=optax.sgd(1e-2), rng=jax.random.key(0),
    )
    mgr = SnapshotManager(str(directory), keep=1)
    try:
        restored = mgr.restore(template)
    finally:
        mgr.close()
    assert restored is not None and restored.step == 1
    kernel = restored.state.params["block0"]["MultiHeadAttention_0"]["qkv"][
        "kernel"]
    assert kernel.shape == (128, 3, 2, 64) and kernel.dtype == jnp.float32
    before = _attn_counts()
    out = model.apply(
        {"params": restored.state.params}, restored.session["images"]
    )
    assert _counted(before, "flash_packed") == {"flash_packed": 1}
    np.testing.assert_allclose(
        np.asarray(out), restored.session["outputs"], rtol=1e-4, atol=1e-5
    )


@pytest.mark.tpu
def test_flash_matches_reference_on_tpu():
    """Kernel parity on real hardware
    (run with BLENDJAX_TEST_TPU=1 pytest -m tpu)."""
    # self-skip beats relying on the marker filter: a pytest invocation
    # overriding -m (e.g. `-m 'not slow'`) runs this on the CPU mesh,
    # where the compiled kernel is not what runs
    if jax.default_backend() != "tpu":
        pytest.skip("the compiled kernel needs a real TPU")
    q, k, v = _qkv(t=1200, h=4, d=64, dtype=jnp.bfloat16)
    for causal in (False, True):
        out = local_attention(q, k, v, causal=causal, backend="flash")
        ref = reference_attention(q, k, v, causal=causal)
        diff = float(
            jnp.max(jnp.abs(out.astype(jnp.float32)
                            - ref.astype(jnp.float32)))
        )
        # bar is a few bf16 ulps at the output magnitudes (~2-4 on the
        # causal path's early rows, where one ulp is 2^-6)
        assert diff < 2e-2, (causal, diff)


# -- the sliding window: a band mask and a key slab a query block ------------------


def _pallas_calls(fn, *args):
    """``(name, [(rows, keys) of each score product])`` of every
    ``pallas_call`` in ``fn``'s jaxpr, forward and backward."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                body = eqn.params["jaxpr"]
                tiles = [
                    tuple(e.outvars[0].aval.shape) for e in body.eqns
                    if e.primitive.name == "dot_general"
                    and e.params["dimension_numbers"][0] == ((1,), (1,))
                ]
                found.append((eqn.params["name"], tiles))
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) else (
                    value,
                ):
                    sub = getattr(sub, "jaxpr", sub)  # a closed jaxpr's
                    if hasattr(sub, "eqns") and eqn.primitive.name != (
                        "pallas_call"
                    ):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize(
    "t, window, tile_elems",
    [(1000, 200, 128 * 1024), (1000, 64, 128 * 1024), (300, 100, None),
     (1000, 1000, 128 * 1024), (1000, 1500, 128 * 1024)],
    ids=["T1000-W200-clamped-both-ends", "T1000-W64", "T300-padded-q",
         "T1000-W-at-T", "T1000-W-above-T"],
)
def test_windowed_kernel_matches_reference(monkeypatch, t, window,
                                           tile_elems):
    """The windowed pair equals ``reference_attention`` with the window,
    the output and the three gradients: at a T no lane block divides
    (1,000 keys in 1,024 lanes), blocks of 128 query rows whose slabs are
    clamped at 0 (the first three) and at the padded keys' end (the last
    two), a Q padded in HBM (300 tokens), and a window at or above T,
    which is causal attention and takes today's kernels."""
    if tile_elems:
        monkeypatch.setattr(A, "FLASH_TILE_ELEMS", tile_elems)
    q, k, v = _qkv(t=t, b=1)
    w = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)
    before = _attn_counts().get("attn.path.window", 0)
    loss, grads = _loss_and_grads(
        lambda *a: local_attention(*a, causal=True, backend="flash",
                                   window=window),
        q, k, v, w,
    )
    banded = window < t
    assert _attn_counts().get("attn.path.window", 0) == before + banded
    want, want_grads = _loss_and_grads(
        lambda *a: reference_attention(*a, causal=True, window=window),
        q, k, v, w,
    )
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for got, ref in zip(grads, want_grads):
        assert got.shape == ref.shape
        np.testing.assert_allclose(got, ref, atol=5e-6)
    names = {name for name, _ in _pallas_calls(
        lambda *a: _loss_and_grads(
            lambda *b: local_attention(*b, causal=True, backend="flash",
                                       window=window), *a, w)[0],
        q, k, v,
    )}
    assert names == ({"flash_attention_window_fwd",
                      "flash_attention_window_bwd"} if banded
                     else {"flash_attention_fwd", "flash_attention_bwd"})


@pytest.mark.parametrize("t, h, kv, window, tile_elems", [
    (256, 2, 2, None, None), (512, 2, 2, 100, 128 * 256),
    (256, 4, 2, 64, None),
], ids=["causal", "windowed", "grouped-query"])
def test_kept_kernel_outputs_give_the_same_gradients(monkeypatch, t, h, kv,
                                                     window, tile_elems):
    """Under ``jax.checkpoint`` with a policy that keeps the three-tensor
    kernel's output and its log-sum-exp (one column a head, which the
    backward kernel reads as it is) the forward kernel runs once where
    plain ``jax.checkpoint`` runs it twice; the gradients are the same
    bits as without ``jax.checkpoint`` and equal
    ``reference_attention``'s: causal, windowed (four query blocks of
    128 rows, each on its slab of 384 of the 512 keys) and grouped-query
    (K/V heads repeated for two query heads each)."""
    if tile_elems:
        monkeypatch.setattr(A, "FLASH_TILE_ELEMS", tile_elems)
    q, k, v = _qkv(t=t, b=1, h=h)
    k, v = k[:, :, :kv], v[:, :, :kv]
    w = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)

    def attend(backend):
        def fn(q, k, v):
            k, v = (jnp.repeat(a, h // kv, axis=2) for a in (k, v))
            if backend == "xla":
                return reference_attention(q, k, v, causal=True,
                                           window=window)
            return local_attention(q, k, v, causal=True, backend="flash",
                                   window=window)

        return fn

    def grad(fn, **remat):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

        if remat:
            loss = jax.checkpoint(loss, **remat)
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2)))

    kept = jax.checkpoint_policies.save_only_these_names(
        RESIDUAL_ATTN_O, RESIDUAL_ATTN_LSE
    )
    fwd = "flash_attention_fwd" if window is None else (
        "flash_attention_window_fwd"
    )
    for policy, forwards in ((kept, 1), (None, 2)):
        calls = [name for name, _ in _pallas_calls(
            grad(attend("flash"), policy=policy), q, k, v
        )]
        assert calls.count(fwd) == forwards, calls
        assert len(calls) == forwards + 1, calls
    loss, grads = grad(attend("flash"), policy=kept)(q, k, v)
    plain_loss, plain_grads = grad(attend("flash"))(q, k, v)
    assert loss == plain_loss
    for got, plain in zip(grads, plain_grads):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(plain))
    want, want_grads = grad(attend("xla"))(q, k, v)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for got, ref in zip(grads, want_grads):
        assert got.shape == ref.shape
        np.testing.assert_allclose(got, ref, atol=5e-6)


def test_a_window_narrows_the_reference_to_its_band():
    q, k, v = _qkv(t=40, b=1, h=1)
    got = reference_attention(q, k, v, causal=True, window=1)
    np.testing.assert_allclose(got, v, rtol=1e-6)  # each row sees itself
    np.testing.assert_array_equal(
        reference_attention(q, k, v, causal=True, window=40),
        reference_attention(q, k, v, causal=True),
    )
    with pytest.raises(ValueError, match="causal"):
        local_attention(q, k, v, window=8)


def test_window_slab_covers_the_band_and_no_more():
    """At the benchmark's sliding layer (4,800 tokens, blocks of 400,
    window 2,048) every query block reads 2,688 of the 4,864 padded keys,
    ``round_up(block_q + window - 1, 128) + 128``, from a lane-aligned
    start that holds its whole band and stays inside the keys."""
    t, window = 4800, 2048
    blocks = flash_block_sizes(t, t, jnp.bfloat16)
    assert (blocks.block_q, blocks.padded_kv) == (400, 4864)
    bound = A._round_up(blocks.block_q + window - 1, 128) + 128
    assert A.window_keys(blocks.block_q, window, blocks.padded_kv) == bound
    assert bound == 2688
    for i in range(t // blocks.block_q):
        start, keys = A._window_slab(jnp.int32(i), blocks.block_q, window, t)
        start = int(start)
        row0, row1 = i * blocks.block_q, (i + 1) * blocks.block_q - 1
        assert keys == bound and start % 128 == 0
        assert 0 <= start <= max(row0 - window + 1, 0)
        assert row1 < start + keys <= blocks.padded_kv
    # a window as long as the padded keys reads them all
    assert A.window_keys(400, 4800, 4864) == 4864


@pytest.mark.parametrize("window, keys", [(200, 512), (64, 384)])
def test_the_launched_kernels_read_only_the_slab(monkeypatch, window, keys):
    """The score products inside the windowed pair, forward and
    backward, are ``[block_q, window_keys]``, not ``[block_q,
    padded_kv]``: 128 query rows against ``keys`` of 1,024."""
    monkeypatch.setattr(A, "FLASH_TILE_ELEMS", 128 * 1024)
    q, k, v = _qkv(t=1000, b=1)
    w = jnp.ones(q.shape, jnp.float32)
    calls = _pallas_calls(
        lambda *a: _loss_and_grads(
            lambda *b: local_attention(*b, causal=True, backend="flash",
                                       window=window), *a, w)[0],
        q, k, v,
    )
    assert keys == A._round_up(128 + window - 1, 128) + 128
    assert sorted(name for name, _ in calls) == [
        "flash_attention_window_bwd", "flash_attention_window_fwd"]
    for _, tiles in calls:
        assert tiles and all(tile == (128, keys) for tile in tiles), tiles
