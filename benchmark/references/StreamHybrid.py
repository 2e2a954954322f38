"""The ``nemotron_h`` layer stack as StreamHybrid runs it, plain: float32
``jax.numpy`` throughout, no kernel, no chunking, no sorting; written
from the published configuration
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
``config.json``) and the Mamba-2 paper (Dao & Gu, arXiv:2405.21060).
Parameters are read from the flax tree by name; nothing of
``blendjax.models`` or ``blendjax.ops`` is imported.

``h`` is (B, T, C). Every layer is ``h <- h + mixer(RMSNorm(h))`` with
one mixer, by the letter of ``pattern``; ``RMSNorm(x) = x rsqrt(mean(x^2)
+ eps) w``; after the last layer ``norm_f``. No projection has a bias;
the convolution has one.

``M``, Mamba-2. ``in_proj`` -> ``z`` (H P), ``xBC`` (H P + 2 G N), ``dt``
(H). ``xBC <- silu(conv(xBC))``, a causal depthwise convolution over the
``conv_kernel`` latest tokens; split into ``x`` (H, P), ``B`` (G, N), ``C``
(G, N); head ``h`` reads group ``h // (H / G)``. ``dt <- softplus(dt +
dt_bias)``, ``A = -exp(A_log)`` a head. The recurrence from ``S_0 = 0``

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,    y_t = S_t C_t + D x_t

is computed in its quadratic form: one T x T matrix a head,
``exp(sum_{s < r <= t} dt_r A)`` for ``s <= t`` and 0 above the diagonal,
times ``C_t . B_s``, applied to ``dt_s x_s``. That is the recurrence
unrolled (``S_t = sum_{s <= t} exp(sum_{s < r <= t} dt_r A) dt_s x_s (x)
B_s``) and depends on no chunk size. Then ``y <- RMSNorm_g(y silu(z))``
with the mean square over each of the G groups of channels, and
``out_proj``.

``E``, experts. ``s = sigmoid(x W_r)`` over all ``num_experts``; the
``experts_per_token`` with the largest ``s + b`` (``b`` the selection
bias); weights ``s`` there over their sum + 1e-20, times
``routed_scaling``. An expert is ``W_down relu(W_up x)^2``. The output is
the sum over the chosen experts THAT ARE HELD HERE (``experts_held`` from
``expert_offset`` on: one chip's share of the deployment the
configuration states) of weight x expert(x), plus the shared expert; what
the absent experts would add is left out, as in the program. Computed
densely: every held expert over every token, times the token's weight
for it (0 where it was not chosen).

``*``, attention. ``q`` to ``num_heads`` heads of ``head_dim``, ``k`` and
``v`` to ``num_kv_heads``; query head ``i`` reads key/value head
``i // (num_heads / num_kv_heads)``; causal softmax of ``q . k /
sqrt(head_dim)``; ``proj``. No rotary embedding.

Around the stack, as the configuration's ``departures`` list: the patch
embedding as a matrix product over flattened patches, a learned position
table, mean pooling after ``norm_f``, a 16-output head.

One departure in memory only: each layer runs under ``jax.checkpoint``,
so that in the gradient one layer's T x T tensors (369 MB an image a
Mamba-2 layer at 1,200 tokens) are alive at a time. The values and the
gradients are those of the plain composition.
"""

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps, groups=1):
    g = x.reshape(*x.shape[:-1], groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(x.shape) * scale


def _relu2(x):
    return jnp.maximum(x, 0.0) ** 2


def _mamba2(p, u, *, heads, head_dim, groups, state, eps):
    b, t, _ = u.shape
    inner = heads * head_dim
    zxbcdt = u @ p["in_proj"]["kernel"]
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * groups * state]
    dt = zxbcdt[..., 2 * inner + 2 * groups * state:]
    taps = p["conv_kernel"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_bias"] + sum(
        p["conv_kernel"][k] * padded[:, k:k + t] for k in range(taps)
    ))
    x = xbc[..., :inner].reshape(b, t, heads, head_dim)
    bm = xbc[..., inner:inner + groups * state].reshape(b, t, groups, state)
    cm = xbc[..., inner + groups * state:].reshape(b, t, groups, state)
    bm, cm = (jnp.repeat(m, heads // groups, axis=2) for m in (bm, cm))
    dt = jax.nn.softplus(dt + p["dt_bias"])                   # (B, T, H)
    a = -jnp.exp(p["A_log"])
    cum = jnp.cumsum(dt * a, axis=1).transpose(0, 2, 1)       # (B, H, T)
    # log decay from s to t: sum over s < r <= t of dt_r A
    diff = cum[:, :, :, None] - cum[:, :, None, :]            # (B, H, T, S)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
    scores = jnp.einsum("bthn,bshn->bhts", cm, bm) * decay
    y = jnp.einsum("bhts,bshp->bthp", scores, dt[..., None] * x)
    y = y + p["D"][:, None] * x
    y = y.reshape(b, t, inner) * jax.nn.silu(z)
    y = _rms_norm(y, p["norm"]["scale"], eps, groups)
    return y @ p["out_proj"]["kernel"]


def _experts(p, u, *, per_token, scaling, held, offset):
    b, t, c = u.shape
    x = u.reshape(b * t, c)
    scores = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(scores + p["e_score_correction_bias"], per_token)
    weights = jnp.take_along_axis(scores, chosen, axis=1)
    weights = scaling * weights / (weights.sum(axis=1, keepdims=True) + 1e-20)
    out = _relu2(x @ p["shared_up"]["kernel"]) @ p["shared_down"]["kernel"]
    for e in range(held):
        weight = jnp.sum(jnp.where(chosen == e + offset, weights, 0.0), axis=1)
        out = out + weight[:, None] * (
            _relu2(x @ p["experts_up"][e]) @ p["experts_down"][e]
        )
    return out.reshape(b, t, c)


def _attention(p, u, *, heads, kv_heads):
    b, t, _ = u.shape
    q = jnp.einsum("btc,chd->bthd", u, p["q"]["kernel"])
    k = jnp.einsum("btc,chd->bthd", u, p["k"]["kernel"])
    v = jnp.einsum("btc,chd->bthd", u, p["v"]["kernel"])
    k, v = (jnp.repeat(m, heads // kv_heads, axis=2) for m in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1])
    )
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(b, t, -1) @ p["proj"]["kernel"]


def forward(params, images, *, patch, pattern, mamba_num_heads,
            mamba_head_dim, ssm_state_size, n_groups, num_heads,
            num_kv_heads, num_experts, experts_per_token,
            routed_scaling=1.0, experts_held=None, expert_offset=0,
            norm_eps=1e-5, **_):
    x = images.astype(jnp.float32) / 255.0
    b, h, w, c = x.shape
    gh, gw = h // patch, w // patch
    x = x.reshape(b, gh, patch, gw, patch, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, gh * gw, patch * patch * c)
    pe = params["patch_embed"]
    x = x @ pe["kernel"].reshape(patch * patch * c, -1) + pe["bias"]
    x = x + params["pos_embed"]
    held = num_experts if experts_held is None else experts_held
    mixers = {
        "M": lambda p, u: _mamba2(
            p, u, heads=mamba_num_heads, head_dim=mamba_head_dim,
            groups=n_groups, state=ssm_state_size, eps=norm_eps,
        ),
        "E": lambda p, u: _experts(
            p, u, per_token=experts_per_token, scaling=routed_scaling,
            held=held, offset=expert_offset,
        ),
        "*": lambda p, u: _attention(
            p, u, heads=num_heads, kv_heads=num_kv_heads
        ),
    }
    for i, kind in enumerate(pattern):
        def layer(p, x, mixer=mixers[kind]):
            return x + mixer(
                p["mixer"], _rms_norm(x, p["norm"]["scale"], norm_eps)
            )

        x = jax.checkpoint(layer)(params[f"layer{i}"], x)
    x = _rms_norm(x, params["norm_f"]["scale"], norm_eps).mean(axis=1)
    return x @ params["head"]["kernel"] + params["head"]["bias"]
