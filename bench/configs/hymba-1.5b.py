"""Plain float32 reference of hymba-1.5b as the program runs it.

Per layer (arXiv:2411.13676: attention heads and SSM heads side by side on
the same input, each path normalised, then averaged; the program uses a
sliding window in every layer, where Hymba keeps three global layers):

    h = RMSNorm(x)
    a = softmax(RoPE(h Wq) RoPE(h Wk)^T / sqrt(hd) + window mask) (h Wv) Wo
        GQA: query head i reads key/value head i // (H / KV); position t
        sees positions t - window + 1 .. t
    u, z = split(h W_in);  u = silu(u);  dt = softplus(h W_dt + dt_bias)
    h_t = exp(dt_t A) h_{t-1} + (dt_t u_t) B_t^T,  y_t = h_t C_t,  A = -exp(a_log)
    s = ((y + d_skip u) * silu(z)) W_out
    x = x + (RMSNorm(a) + RMSNorm(s)) / 2
    x = x + (silu(RMSNorm(x) Wg) * (RMSNorm(x) Wi)) Wo

then RMSNorm, the LM head and the mean cross-entropy.  Attention scores
every pair and masks; the SSM runs one position at a time.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib.nn import cross_entropy, dot, rms_norm, scan_layers  # noqa: E402

GROUP = "groups/hymba/"


def param_shapes(c: dict) -> dict:
    """``{path in the program's tree: (shape, init rule)}``; the SSM's A and
    dt follow the published (S4D-real, Mamba) init."""
    d, f, v, n = c["d_model"], c["d_ff"], c["vocab_size"], c["n_layers"]
    h, kv, hd, st = c["n_heads"], c["n_kv_heads"], c["head_dim"], c["ssm_state"]
    di = c["ssm_inner"]
    scaled = lambda fan_in: ("normal", fan_in ** -0.5)
    scale = ("uniform", 0.8, 1.2)
    layer = {
        "ln1/scale": ((d,), scale),
        "ln_a/scale": ((d,), scale),
        "ln_s/scale": ((d,), scale),
        "ln2/scale": ((d,), scale),
        "attn/wq": ((d, h, hd), scaled(d)),
        "attn/wk": ((d, kv, hd), scaled(d)),
        "attn/wv": ((d, kv, hd), scaled(d)),
        "attn/wo": ((h, hd, d), scaled(h * hd)),
        "mamba/w_in": ((d, 2 * di), scaled(d)),
        "mamba/w_dt": ((d, di), ("normal", 0.1 * d ** -0.5)),
        "mamba/dt_bias": ((di,), ("inv_softplus_loguniform", 1e-3, 1e-1)),
        "mamba/w_b": ((d, st), scaled(d)),
        "mamba/w_c": ((d, st), scaled(d)),
        "mamba/a_log": ((di, st), ("log_arange",)),
        "mamba/d_skip": ((di,), ("uniform", 0.5, 1.5)),
        "mamba/w_out": ((di, d), scaled(di)),
        "mlp/wi": ((d, f), scaled(d)),
        "mlp/wg": ((d, f), scaled(d)),
        "mlp/wo": ((f, d), scaled(f)),
    }
    out = {GROUP + k: ((n,) + s, rule) for k, (s, rule) in layer.items()}
    out.update({
        "embed/table": ((v, d), ("normal", 0.05)),
        "final_norm/scale": ((d,), scale),
        "unembed/w": ((d, v), scaled(d)),
    })
    return out


def rope(x, theta):
    """Rotate-half RoPE; x: (b, s, heads, hd)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def attention(p, x, c, cast):
    s = x.shape[1]
    group = c["n_heads"] // c["n_kv_heads"]
    q = rope(dot("bsd,dhk->bshk", x, p["attn/wq"], cast), c["rope_theta"])
    k = rope(dot("bsd,dhk->bshk", x, p["attn/wk"], cast), c["rope_theta"])
    v = dot("bsd,dhk->bshk", x, p["attn/wv"], cast)
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = dot("bqhk,bthk->bhqt", q, k, cast) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    pos = jnp.arange(s)
    seen = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None]
                                             - c["window"])
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = dot("bhqt,bthk->bqhk", probs, v, cast)
    return dot("bshk,hkd->bsd", out, p["attn/wo"], cast)


def ssm(p, x, cast):
    m = lambda name: p["mamba/" + name]
    u, z = jnp.split(dot("bsd,de->bse", x, m("w_in"), cast), 2, axis=-1)
    u = jax.nn.silu(u)
    dt = jax.nn.softplus(dot("bsd,de->bse", x, m("w_dt"), cast) + m("dt_bias"))
    bb = dot("bsd,dn->bsn", x, m("w_b"), cast)
    cc = dot("bsd,dn->bsn", x, m("w_c"), cast)
    a = -jnp.exp(m("a_log"))

    def step(state, xs):
        ut, dtt, bt, ct = xs
        state = (jnp.exp(dtt[..., None] * a) * state
                 + (dtt * ut)[..., None] * bt[:, None, :])
        return state, dot("bdn,bn->bd", state, ct, cast)

    b, _, di = u.shape
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (u, dt, bb, cc))
    _, ys = jax.lax.scan(step, jnp.zeros((b, di, a.shape[-1]), jnp.float32), xs)
    y = (jnp.moveaxis(ys, 0, 1) + m("d_skip") * u) * jax.nn.silu(z)
    return dot("bse,ed->bsd", y, m("w_out"), cast)


def block(p, x, c, cast):
    h = rms_norm(x, p["ln1/scale"])
    x = x + 0.5 * (rms_norm(attention(p, h, c, cast), p["ln_a/scale"])
                   + rms_norm(ssm(p, h, cast), p["ln_s/scale"]))
    h = rms_norm(x, p["ln2/scale"])
    mlp = (jax.nn.silu(dot("bsd,df->bsf", h, p["mlp/wg"], cast))
           * dot("bsd,df->bsf", h, p["mlp/wi"], cast))
    return x + dot("bsf,fd->bsd", mlp, p["mlp/wo"], cast)


def loss(p: dict, inputs, targets, c: dict, cast):
    """Mean cross-entropy of one agent's rows; ``p`` maps paths to float32."""
    x = jnp.take(p["embed/table"], inputs, axis=0)
    layers = {k[len(GROUP):]: a for k, a in p.items() if k.startswith(GROUP)}
    x = scan_layers(lambda lp, h: block(lp, h, c, cast), x, layers)
    x = rms_norm(x, p["final_norm/scale"])
    return cross_entropy(dot("bsd,dv->bsv", x, p["unembed/w"], cast), targets)


def attended_keys(seq: int, window: int) -> float:
    """Mean number of keys a position attends to under a causal window."""
    return sum(min(t + 1, window) for t in range(seq)) / seq


def flops_per_token(c: dict, seq: int) -> float:
    """Forward FLOPs per token that the model requires: the projections,
    QK^T and AV over the keys inside the window (not the band the program
    computes), the SSM's state update and read-out (per inner channel and
    state entry), the MLP and the LM head; no embedding gather, no
    recomputation."""
    d, f, di, st = c["d_model"], c["d_ff"], c["ssm_inner"], c["ssm_state"]
    h, kv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    attn = 2 * d * h * hd + 2 * d * kv * hd + \
        2 * h * hd * attended_keys(seq, c["window"])
    ssm_ops = d * 2 * di + d * di + 2 * d * st + di * d + 2 * di * st
    mlp = 3 * d * f
    return 2.0 * (c["n_layers"] * (attn + ssm_ops + mlp) + d * c["vocab_size"])
