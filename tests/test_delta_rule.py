"""The chunked gated delta rule is the recurrence it was written from.

:func:`blendjax.ops.delta_rule.gated_delta_rule_chunked` (the UT
transform inside chunks, a carried state between them) against
:func:`gated_delta_rule_sequential` (one token at a time, float32): the
output, the final state and every input's gradient, at lengths a chunk
divides and does not, with steps ``beta`` across (0, 2), weak and strong
decays, from a given state, and with bf16 operands; the reason each
tolerance is what it is.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from blendjax.ops.attention import batch_sharded_over
from blendjax.ops.delta_rule import (
    _BNN,
    _BNT,
    _BTN,
    DELTA_VMEM_BYTES,
    _dot,
    _group_size,
    _split,
    _vmem_bytes,
    auto_picks_kernel,
    delta_rule_kernel_supported,
    gated_delta_rule_chunked,
    gated_delta_rule_sequential,
)
from blendjax.utils.metrics import (
    DECODER_RESIDUALS,
    DELTA_RESIDUALS,
    RESIDUAL_GDN_O,
    metrics,
    saved_residual,
)

# float32: the two forms sum the same products in another order (a
# chunk's triangular solve and its 8-64 terms against a running state):
# measured up to 3e-7 of the largest value and 2e-6 of the largest entry
# of q's, k's, v's and beta's gradients; the decays' gradient sums a
# whole state's products a token, which cancel where the decays are
# strong, and reads up to 1.1e-5. The bars are hybrid's (2e-6 and 5e-5).
F32_TOL = (2e-6, 5e-5)
# bf16 operands: q, k, v rounded to 8 bits (2^-9 relative each) and the
# state and V' rounded once a chunk where the four products read them,
# float32 accumulation, the UT transform in float32 either way: measured
# 3.4e-3 (weak decays) and 8.2e-3 (strong) of the largest value against
# the float32 recurrence on the bf16-rounded inputs, so 3e-2, ssd's bf16
# bar.
BF16_TOL = 3e-2
H, K, V = 3, 8, 16
# beta = 2 sigmoid(z): the layer's range, the eigenvalues of I - beta k k^T
# down to -1
BETAS = {"small": (-3.0, 1.0), "wide": (0.0, 2.0), "near_two": (4.0, 0.5)}
# g = -softplus(z + shift): a decay near 1 a token, or strong enough that
# a chunk's running sum reaches -100 and exp(gamma) underflows
DECAYS = {"weak": -4.0, "strong": 2.0}


def _inputs(t, batch=2, seed=0, beta="wide", decay="weak", dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(seed), 5)

    def unit(key):
        x = jax.random.normal(key, (batch, t, H, K))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q, k = unit(keys[0]) * K ** -0.5, unit(keys[1])
    v = jax.random.normal(keys[2], (batch, t, H, V))
    g = -jax.nn.softplus(
        jax.random.normal(keys[3], (batch, t, H)) + DECAYS[decay]
    )
    mean, spread = BETAS[beta]
    b = 2.0 * jax.nn.sigmoid(
        mean + spread * jax.random.normal(keys[4], (batch, t, H))
    )
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, b)


def _rel(got, want):
    got, want = (np.asarray(v, np.float64) for v in (got, want))
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def _weighted(form):
    """A scalar of the output and the final state that weighs every
    entry differently, so each input's gradient is a full one."""

    def loss(*args, **kwargs):
        o, state = form(*args, **kwargs)
        return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(state))

    return loss


@pytest.mark.parametrize("beta", sorted(BETAS))
@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("t, chunk", [
    (32, 8),    # four whole chunks
    (37, 8),    # a last chunk of 5, padded with beta = 0, g = 0 tokens
    (13, 16),   # shorter than a chunk: no carried state
    (100, 32),  # chunks of the layer's kind, a ragged last one
])
def test_chunked_is_the_recurrence(t, chunk, beta, decay):
    args = _inputs(t, beta=beta, decay=decay)
    want_o, want_s = jax.jit(gated_delta_rule_sequential)(*args)
    got_o, got_s = jax.jit(
        lambda *a: gated_delta_rule_chunked(*a, chunk=chunk)
    )(*args)
    assert got_o.shape == want_o.shape == (2, t, H, V)
    assert got_o.dtype == jnp.float32
    assert _rel(got_o, want_o) < F32_TOL[0]
    assert _rel(got_s, want_s) < F32_TOL[0]
    argnums = tuple(range(5))
    want_g = jax.jit(jax.grad(
        _weighted(gated_delta_rule_sequential), argnums
    ))(*args)
    got_g = jax.jit(jax.grad(_weighted(
        lambda *a: gated_delta_rule_chunked(*a, chunk=chunk)
    ), argnums))(*args)
    for name, g, w in zip("q k v g beta".split(), got_g, want_g):
        assert np.abs(np.asarray(w)).max() > 0, name
        assert _rel(g, w) < F32_TOL[1], name


@pytest.mark.parametrize("t, chunk", [(40, 16), (9, 16)])
def test_a_given_state_is_carried_on(t, chunk):
    """From a state, as a second segment of one sequence: the two halves
    run one after the other are the whole, and the gradient reaches the
    state."""
    args = _inputs(2 * t, seed=3)
    first = [a[:, :t] for a in args]
    second = [a[:, t:] for a in args]
    whole_o, whole_s = gated_delta_rule_sequential(*args)
    o1, s1 = gated_delta_rule_chunked(*first, chunk=chunk)
    o2, s2 = gated_delta_rule_chunked(*second, chunk=chunk, initial_state=s1)
    assert _rel(jnp.concatenate([o1, o2], axis=1), whole_o) < F32_TOL[0]
    assert _rel(s2, whole_s) < F32_TOL[0]

    def from_state(form):
        return jax.grad(
            lambda s: _weighted(form)(*second, initial_state=s)
        )(s1)

    want = from_state(gated_delta_rule_sequential)
    got = from_state(
        lambda *a, **kw: gated_delta_rule_chunked(*a, chunk=chunk, **kw)
    )
    assert np.abs(np.asarray(want)).max() > 0
    assert _rel(got, want) < F32_TOL[1]


@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_bf16_operands_stay_near_float32(decay):
    """bf16 q, k, v (as the layer passes them) against the float32
    recurrence on the same rounded inputs; the output stays float32."""
    args = _inputs(96, decay=decay, dtype=jnp.bfloat16)
    got, _ = gated_delta_rule_chunked(*args, chunk=32)
    want, _ = gated_delta_rule_sequential(*args)
    assert got.dtype == jnp.float32
    assert F32_TOL[0] < _rel(got, want) < BF16_TOL


def test_padding_tokens_leave_the_state_as_it_was():
    """A length the chunk does not divide: the final state is the
    recurrence's after the last real token, not after the pad."""
    args = _inputs(20, seed=5)
    _, want = gated_delta_rule_sequential(*args)
    for chunk in (8, 16, 64):
        _, got = gated_delta_rule_chunked(*args, chunk=chunk)
        assert _rel(got, want) < F32_TOL[0], chunk


def test_the_path_and_its_scope_are_named_once_a_trace():
    args = _inputs(24)
    before = metrics.report()["counters"].get("gdn.path.chunked", 0)
    text = jax.jit(
        lambda *a: gated_delta_rule_chunked(*a, chunk=8)[0]
    ).lower(*args).as_text(debug_info=True)
    assert metrics.report()["counters"]["gdn.path.chunked"] == before + 1
    assert "delta_rule" in text


# -- the kernel pair (interpreter mode here; what the chip's compiler says
# of it is tests/test_tpu_compile.py's) ---------------------------------------

# The cell's head widths, three heads (a group of three, or of one when
# the blocking batches fewer).
WIDE = dict(heads=3, key_dim=96, value_dim=192)
# float32 at K 96, V 192 against the recurrence token by token: every
# contraction sums 12 times the terms of the tiny widths', and the kernel
# inverts (I + A) by doubling blocks where the XLA form substitutes:
# measured up to 2.1e-6 of the largest value (the XLA form 2.0e-6 on the
# same inputs) and 1.5e-6 of the largest entry of a gradient against the
# XLA form's autodiff. So 1e-5 for both, five times the readings.
WIDE_F32_TOL = 1e-5
# bf16 operands: the kernel rounds where the XLA form rounds and keeps
# float32 where it keeps float32, so its distance from the float32
# recurrence on the same rounded inputs is the XLA form's (measured equal
# to three digits, 2.3e-3 to 4.2e-3 of the largest value), and so is each
# gradient's from float32 autodiff: measured 0.53 to 1.05 times the XLA
# form's (the kernel keeps float32 some products' results that the XLA
# form rounds to bf16). Rounding do, the carried state's cotangent or
# V''s to bf16 where they meet a product read up to 1.26 times on the
# same inputs (q's gradient, strong decays), so 1.2.
BF16_GRAD_OVER_XLA = 1.2
# g = 0: no decay at all, beside DECAYS' weak and strong
WIDE_DECAYS = {"none": None, **DECAYS}
# beta = 2 sigmoid(z): near 0 (the state barely moves) and near 2 (the
# eigenvalues of I - beta k k^T near -1)
WIDE_BETAS = {"near_zero": (-4.0, 1.0), "near_two": BETAS["near_two"]}


def _wide(t, batch=2, seed=0, beta="near_two", decay="weak",
          dtype=jnp.float32, heads=3, key_dim=96, value_dim=192):
    keys = jax.random.split(jax.random.key(seed), 5)

    def unit(key, width):
        x = jax.random.normal(key, (batch, t, heads, width))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(keys[0], key_dim) * key_dim ** -0.5
    k = unit(keys[1], key_dim)
    v = jax.random.normal(keys[2], (batch, t, heads, value_dim))
    if WIDE_DECAYS[decay] is None:
        g = jnp.zeros((batch, t, heads))
    else:
        g = -jax.nn.softplus(
            jax.random.normal(keys[3], (batch, t, heads)) + DECAYS[decay])
    mean, spread = WIDE_BETAS[beta]
    b = 2.0 * jax.nn.sigmoid(
        mean + spread * jax.random.normal(keys[4], (batch, t, heads)))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, b)


def _grads(form, args):
    return jax.jit(jax.grad(_weighted(form), tuple(range(5))))(*args)


def _kernel(*args, chunk=64, **kwargs):
    return gated_delta_rule_chunked(*args, chunk=chunk, backend="kernel",
                                    **kwargs)


@pytest.mark.parametrize("beta", sorted(WIDE_BETAS))
@pytest.mark.parametrize("decay", sorted(WIDE_DECAYS))
@pytest.mark.parametrize("t", [
    128,  # two whole chunks
    150,  # a ragged last chunk of 22, masked in the kernel
    40,   # shorter than a chunk: no carried state
])
def test_the_kernel_is_the_recurrence(t, decay, beta):
    """The kernel pair at the cell's head widths: the output and the final
    state against the recurrence token by token, every input's gradient
    against the XLA form's autodiff, float32."""
    args = _wide(t, beta=beta, decay=decay)
    want_o, want_s = jax.jit(gated_delta_rule_sequential)(*args)
    got_o, got_s = jax.jit(_kernel)(*args)
    assert got_o.shape == want_o.shape and got_o.dtype == jnp.float32
    assert got_s.shape == want_s.shape == (2, 3, 96, 192)
    assert _rel(got_o, want_o) < WIDE_F32_TOL
    assert _rel(got_s, want_s) < WIDE_F32_TOL
    want_g = _grads(lambda *a: gated_delta_rule_chunked(
        *a, chunk=64, backend="xla"), args)
    for name, g, w in zip("q k v g beta".split(), _grads(_kernel, args),
                          want_g):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert np.abs(np.asarray(w)).max() > 0, name
        assert _rel(g, w) < WIDE_F32_TOL, name


@pytest.mark.parametrize("decay", sorted(WIDE_DECAYS))
def test_the_kernel_keeps_the_xla_forms_precision_at_bf16(decay):
    """bf16 q, k, v as the mixer passes them: the kernel's output is as
    close to the float32 recurrence on the same rounded inputs as the XLA
    form's, and so is each gradient, float32 autodiff of the XLA form on
    those inputs the truth."""
    args = _wide(150, decay=decay, dtype=jnp.bfloat16, seed=2)
    wide = tuple(x.astype(jnp.float32) for x in args)
    want, _ = jax.jit(gated_delta_rule_sequential)(*wide)
    got, _ = jax.jit(_kernel)(*args)
    xla, _ = jax.jit(lambda *a: gated_delta_rule_chunked(
        *a, chunk=64, backend="xla"))(*args)
    assert got.dtype == jnp.float32
    assert F32_TOL[0] < _rel(got, want) < 1.1 * _rel(xla, want)
    assert _rel(got, want) < BF16_TOL
    def xla_form(*a):
        return gated_delta_rule_chunked(*a, chunk=64, backend="xla")

    truth = _grads(xla_form, wide)
    for name, g, x_g, w, x in zip("q k v g beta".split(),
                                  _grads(_kernel, args),
                                  _grads(xla_form, args), truth, args):
        assert g.dtype == x.dtype, name
        assert _rel(g, w) < BF16_GRAD_OVER_XLA * _rel(x_g, w), name


def test_the_kernels_padding_leaves_the_prefix_alone():
    """A sequence cut after any token gives that prefix's outputs: what a
    ragged last block holds past the end never reaches back (the
    interpreter fills it with NaN)."""
    args = _wide(150, seed=4)
    whole, _ = jax.jit(_kernel)(*args)
    part, state = jax.jit(_kernel)(*(x[:, :100] for x in args))
    _, want = gated_delta_rule_sequential(*(x[:, :100] for x in args))
    assert np.isfinite(np.asarray(whole)).all()
    assert _rel(part, whole[:, :100]) < WIDE_F32_TOL
    assert _rel(state, want) < WIDE_F32_TOL


@pytest.mark.parametrize("dims", ["nn", "nt", "tn"])
def test_a_split_cotangent_meets_bf16_at_float32(dims):
    """``_split``'s three bfloat16 pieces of a float32 cotangent, each
    multiplied by a bfloat16 operand, give the float32 product (as the
    XLA form's autodiff takes it at HIGHEST) and not the product of the
    cotangent rounded to bf16, on either side and in the three layouts
    the backward uses."""
    keys = jax.random.split(jax.random.key(3), 2)
    x = jax.random.normal(keys[0], (2, 64, 192), jnp.float32)
    y = jax.random.normal(keys[1], (2, 64 if dims == "tn" else 96, 192),
                          jnp.bfloat16)
    if dims == "nn":
        y = jnp.swapaxes(y, 1, 2)
    layout = {"nn": _BNN, "nt": _BNT, "tn": _BTN}[dims]
    if dims == "tn":
        x, y = y, x  # the cotangent on the right
    exact = jax.lax.dot_general(
        x.astype(jnp.float32), y.astype(jnp.float32), layout,
        precision=jax.lax.Precision.HIGHEST)
    split = [_split(a, jnp.bfloat16) if a.dtype == jnp.float32 else a
             for a in (x, y)]
    rounded = [a.astype(jnp.bfloat16) for a in (x, y)]
    assert _rel(_dot(*split, layout), exact) < 1e-6
    assert _rel(_dot(*rounded, layout), exact) > 1e-3
    assert _split(x, jnp.float32) is x


@pytest.mark.parametrize("which", [0, 1], ids=["of_o", "of_state"])
def test_the_kernels_backward_reads_float32_cotangents(which):
    """The backward is linear in the cotangents of ``o`` and of the final
    state, and keeps them float32 where they meet a product (as does the
    XLA form's autodiff): digits below bf16's resolution, added to one
    cotangent that bf16 holds exactly, move the float32 gradients (of g
    and beta, which every product with the cotangent of ``o``, of the
    carried state and of ``V'`` feeds) by what those digits alone give.
    A backward that rounded any of the three to bf16 would drop them."""
    args = _wide(150, dtype=jnp.bfloat16, seed=5)
    _, vjp = jax.vjp(lambda g, b: _kernel(*args[:3], g, b), *args[3:])
    keys = jax.random.split(jax.random.key(6), 3)
    whole = [jax.random.normal(key, shape).astype(jnp.bfloat16)
             .astype(jnp.float32) for key, shape in zip(
                 keys[:2], ((2, 150, 3, 192), (2, 3, 96, 192)))]
    # 2^-9.5 of each entry, under half a bf16 step: rounds away in bf16
    small = [jnp.zeros_like(x) for x in whole]
    small[which] = whole[which] * 2.0 ** -9.5 * jax.random.rademacher(
        keys[2], whole[which].shape, jnp.float32)
    both = vjp(tuple(a + b for a, b in zip(whole, small)))
    for name, got_both, got_whole, want in zip(
            ("g", "beta"), both, vjp(tuple(whole)), vjp(tuple(small))):
        assert np.abs(np.asarray(want)).max() > 0, name
        # float32 rounding of the two sums: up to 5.3e-3 of the digits'
        # own effect; a backward that drops the digits reads 1 or more
        assert _rel(got_both - got_whole, want) < 0.1, name


def test_the_cells_heads_are_batched_three_at_a_time():
    """At olmohybrid_replay's layer the backward's grid step fits v5e's
    default scoped VMEM at 3 heads a group and not at 5 (compiled for a
    described v5e: 14.8 to 15.0 MiB and 16.4 to 16.5): the group sets how
    many chains of small products the MXU overlaps, so a change to the
    estimate that moves it is a change of the kernels' speed (the pair
    at 3 heads a group took 6.51 ms a layer on a v5e, at 5 6.07, before
    the backward kept its cotangents float32)."""
    assert _group_size(64, 15, 96, 192, 2) == 3
    assert _vmem_bytes(64, 15, 3, 96, 192, 2) <= DELTA_VMEM_BYTES
    assert _vmem_bytes(64, 15, 5, 96, 192, 2) > DELTA_VMEM_BYTES


def _shapes(batch, t, h, dk, dv, dtype=jnp.bfloat16):
    return (jax.ShapeDtypeStruct((batch, t, h, dk), dtype),
            jax.ShapeDtypeStruct((batch, t, h, dv), dtype))


CELL = (2, 4800, 15, 96, 192)          # olmohybrid_replay's layer
REHEARSAL = (2, 42, 4, 16, 32)         # test_delta_hybrid.py's widths


@pytest.mark.parametrize(
    "shape, chunk, backend, devices, mesh_over, state, picks, supported", [
        (CELL, 64, "tpu", 1, None, False, True, True),
        (CELL, 64, "cpu", 1, None, False, False, True),
        (CELL, 64, "tpu", 1, None, True, False, True),   # a given state
        (REHEARSAL, 16, "tpu", 1, None, False, True, True),
        (CELL, 48, "tpu", 1, None, False, False, False),  # not a power of 2
        (CELL, 8, "tpu", 1, None, False, False, False),   # under bf16's tile
        ((2, 4800, 15, 100, 192), 64, "tpu", 1, None, False, False, False),
        # one head's state and blocks past the scoped VMEM
        ((2, 4800, 16, 512, 1024), 64, "tpu", 1, None, False, False,
         False),
        # several devices and nothing declared: the program may be
        # partitioned, where the lowering refuses a bare kernel
        (CELL, 64, "tpu", 8, None, False, False, True),
        # a declared mesh: per batch shard where its axis divides the batch
        (CELL, 64, "tpu", 8, 2, False, True, True),
        (CELL, 64, "tpu", 8, 8, False, False, True),
        (CELL, 64, "tpu", 8, 1, False, True, True),      # a one-device mesh
    ],
    ids=["cell", "cell-on-cpu", "initial-state", "rehearsal", "chunk48",
         "chunk8", "key-of-100", "too-large", "undeclared-8-devices",
         "mesh2", "mesh8-batch2", "mesh1"],
)
def test_which_inputs_take_the_kernel(monkeypatch, shape, chunk, backend,
                                      devices, mesh_over, state, picks,
                                      supported):
    """The eligibility rule as a truth table: ``auto`` takes the kernel
    on a TPU, where the blocking takes the shape, no initial state is
    given and the placement may hold a kernel; the XLA form everywhere
    else."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    q, v = _shapes(*shape)
    assert delta_rule_kernel_supported(q, v, chunk) == supported
    initial = jnp.zeros(()) if state else None
    mesh = None if mesh_over is None else jax.sharding.Mesh(
        np.array(jax.devices()[:mesh_over]), ("data",)
    )
    with batch_sharded_over(mesh):
        assert auto_picks_kernel(q, v, chunk, initial) == picks


@pytest.mark.parametrize("backend, counted, other", [
    ("auto", "gdn.path.chunked", "gdn.path.kernel"),
    ("kernel", "gdn.path.kernel", "gdn.path.chunked"),
], ids=["xla", "kernel"])
def test_the_kernel_path_is_counted_once_a_trace(backend, counted, other):
    """One of the two counters a traced call, never both; ``auto`` off a
    TPU is the XLA form; both run under the scope ``delta_rule``."""
    args = _wide(64, batch=1)
    before = metrics.report()["counters"]
    fn = jax.jit(lambda *a: gated_delta_rule_chunked(
        *a, chunk=64, backend=backend)[0])
    text = fn.lower(*args).as_text(debug_info=True)
    fn.lower(*args)
    after = metrics.report()["counters"]
    assert after[counted] == before.get(counted, 0) + 1
    assert after.get(other, 0) == before.get(other, 0)
    assert "delta_rule" in text


def test_an_explicit_kernel_refuses_what_it_cannot_take():
    args = _wide(16, batch=1, heads=1, key_dim=8, value_dim=8)
    with pytest.raises(ValueError, match="power of two"):
        gated_delta_rule_chunked(*args, chunk=12, backend="kernel")
    with pytest.raises(ValueError, match="from a given state"):
        gated_delta_rule_chunked(*args, chunk=8, backend="kernel",
                                 initial_state=jnp.zeros((1, 1, 8, 8)))
    with pytest.raises(ValueError, match="unknown delta-rule backend"):
        gated_delta_rule_chunked(*args, chunk=8, backend="pallas")


def test_the_kernel_runs_per_batch_shard_of_a_declared_mesh():
    """Under a declared mesh the kernel pair runs through ``shard_map``
    over the batch axis (GSPMD cannot partition a custom call): the same
    value, final state and gradients as the undeclared call."""
    args = _wide(70, seed=6)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
    grad = jax.value_and_grad(_weighted(_kernel), tuple(range(5)))
    want, want_g = jax.jit(grad)(*args)
    with batch_sharded_over(mesh):
        got, got_g = jax.jit(grad)(*args)
    assert _rel(got, want) < 1e-6
    for g, w in zip(got_g, want_g):
        assert _rel(g, w) < 1e-6


@pytest.mark.parametrize("policy, forwards", [
    (None, 2),  # plain remat: the backward runs the forward kernel again
    (jax.checkpoint_policies.save_only_these_names(
        *DECODER_RESIDUALS, *DELTA_RESIDUALS), 1),
], ids=["plain", "saved"])
def test_remat_keeps_the_kernels_states(policy, forwards):
    """Under ``remat`` with StreamDeltaHybrid's policy the states the
    kernel starts each chunk from and each chunk's ``(I + A)^-1`` are kept
    (``gdn_states``, ``gdn_inverse``) beside the output the mixer names
    (``gdn_o``), so the forward kernel runs once: the lowered program
    calls it once, and the value and gradients are the ones without
    ``remat``."""
    args = _wide(100, seed=7)

    def loss(*a):
        o, _ = _kernel(*a)
        return jnp.sum(jnp.sin(saved_residual(o, RESIDUAL_GDN_O)))

    want, want_g = jax.jit(jax.value_and_grad(loss, tuple(range(5))))(*args)
    grad = jax.jit(jax.value_and_grad(
        jax.checkpoint(loss, policy=policy), tuple(range(5))
    ))
    text = grad.lower(*args).as_text()
    assert len(re.findall(r"call @_rule_fwd(_\d+)?\(", text)) == forwards
    assert len(re.findall(r"call @_rule_bwd(_\d+)?\(", text)) == 1
    got, got_g = grad(*args)
    assert _rel(got, want) < 1e-6
    for g, w in zip(got_g, want_g):
        assert _rel(g, w) < 1e-6
