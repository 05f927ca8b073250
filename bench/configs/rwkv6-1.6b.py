"""Plain float32 reference of rwkv6-1.6b (Finch) as the program runs it.

Per layer (arXiv:2404.05892, with the program's two noted departures:
static token-shift interpolation, and an RMS norm of the WKV output in
place of the per-head group norm):

    h = LayerNorm(x)                      token shift: h'[t] = h[t-1]
    r, k, v, g = (h + (h' - h) mu_*) W_*  g = silu(.)
    w = exp(-exp(w0 + tanh((h + (h' - h) mu_w) A) B))
    y_t = r_t . (S_t + (u * k_t) v_t^T),  S_{t+1} = diag(w_t) S_t + k_t v_t^T
    x = x + (RMSNorm(y) * g) W_o
    h = LayerNorm(x)
    x = x + sigmoid((h + (h' - h) mu_cr) W_cr) * (relu((h + (h' - h) mu_ck) W_ck)^2 W_cv)

then LayerNorm, the LM head and the mean cross-entropy.  The WKV
recurrence runs one position at a time, as written above.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib.nn import (cross_entropy, dot, layer_norm, rms_norm,  # noqa: E402
                    scan_layers, shift)

GROUP = "groups/rwkv/"


def param_shapes(c: dict) -> dict:
    """``{path in the program's tree: (shape, init rule)}``; the rules follow
    the published init where it is random (decays, bonus, token shift)."""
    d, f, r, v = c["d_model"], c["d_ff"], c["decay_lora"], c["vocab_size"]
    h, hs, n = c["n_heads"], c["head_size"], c["n_layers"]
    scaled = lambda fan_in: ("normal", fan_in ** -0.5)
    ln = {"scale": ((d,), ("uniform", 0.8, 1.2)),
          "bias": ((d,), ("normal", 0.02))}
    layer = {f"ln1/{k}": s for k, s in ln.items()}
    layer.update({f"ln2/{k}": s for k, s in ln.items()})
    for m in ("r", "k", "v", "w", "g"):
        layer[f"time_mix/mu_{m}"] = ((d,), ("uniform", 0.0, 1.0))
    for m in ("wr", "wk", "wv", "wg", "wo"):
        layer[f"time_mix/{m}"] = ((d, d), scaled(d))
    layer.update({
        "time_mix/w0": ((d,), ("uniform", -6.0, -1.0)),
        "time_mix/wA": ((d, r), scaled(d)),
        "time_mix/wB": ((r, d), ("normal", 0.1 * r ** -0.5)),
        "time_mix/u": ((h, hs), ("uniform", -0.1, 1.0)),
        "time_mix/ln_out/scale": ((d,), ("uniform", 0.8, 1.2)),
        "channel_mix/mu_ck": ((d,), ("uniform", 0.0, 1.0)),
        "channel_mix/mu_cr": ((d,), ("uniform", 0.0, 1.0)),
        "channel_mix/wck": ((d, f), scaled(d)),
        "channel_mix/wcv": ((f, d), scaled(f)),
        "channel_mix/wcr": ((d, d), scaled(d)),
    })
    out = {GROUP + k: ((n,) + s, rule) for k, (s, rule) in layer.items()}
    out.update({
        "embed/table": ((v, d), ("normal", 0.05)),
        "final_norm/scale": ln["scale"],
        "final_norm/bias": ln["bias"],
        "unembed/w": ((d, v), scaled(d)),
    })
    return out


def wkv(r, k, v, w, u, cast):
    """The recurrence, one position at a time.  r, k, v, w: (b, s, H, hs)."""
    b, _, h, hs = r.shape

    def step(state, xs):
        rt, kt, vt, wt = xs
        kv = kt[..., :, None] * vt[..., None, :]
        y = dot("bhi,bhij->bhj", rt, state + u[None, :, :, None] * kv, cast)
        return wt[..., :, None] * state + kv, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, w))
    _, ys = jax.lax.scan(step, jnp.zeros((b, h, hs, hs), jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 1)


def block(p, x, c, cast):
    b, s, d = x.shape
    h, hs = c["n_heads"], c["head_size"]
    t = lambda name: p["time_mix/" + name]
    hn = layer_norm(x, p["ln1/scale"], p["ln1/bias"])
    hp = shift(hn)
    mix = lambda mu: hn + (hp - hn) * mu
    heads = lambda a: a.reshape(b, s, h, hs)
    r = heads(dot("bsd,de->bse", mix(t("mu_r")), t("wr"), cast))
    k = heads(dot("bsd,de->bse", mix(t("mu_k")), t("wk"), cast))
    v = heads(dot("bsd,de->bse", mix(t("mu_v")), t("wv"), cast))
    g = jax.nn.silu(dot("bsd,de->bse", mix(t("mu_g")), t("wg"), cast))
    dd = dot("bsr,rd->bsd",
             jnp.tanh(dot("bsd,dr->bsr", mix(t("mu_w")), t("wA"), cast)),
             t("wB"), cast)
    w = heads(jnp.exp(-jnp.exp(t("w0") + dd)))
    y = wkv(r, k, v, w, t("u"), cast).reshape(b, s, d)
    x = x + dot("bse,ed->bsd", rms_norm(y, t("ln_out/scale")) * g, t("wo"), cast)

    cm = lambda name: p["channel_mix/" + name]
    hn = layer_norm(x, p["ln2/scale"], p["ln2/bias"])
    hp = shift(hn)
    kk = jnp.square(jax.nn.relu(
        dot("bsd,df->bsf", hn + (hp - hn) * cm("mu_ck"), cm("wck"), cast)))
    rr = jax.nn.sigmoid(
        dot("bsd,de->bse", hn + (hp - hn) * cm("mu_cr"), cm("wcr"), cast))
    return x + rr * dot("bsf,fd->bsd", kk, cm("wcv"), cast)


def loss(p: dict, inputs, targets, c: dict, cast):
    """Mean cross-entropy of one agent's rows; ``p`` maps paths to float32."""
    x = jnp.take(p["embed/table"], inputs, axis=0)
    layers = {k[len(GROUP):]: a for k, a in p.items() if k.startswith(GROUP)}
    x = scan_layers(lambda lp, h: block(lp, h, c, cast), x, layers)
    x = layer_norm(x, p["final_norm/scale"], p["final_norm/bias"])
    return cross_entropy(dot("bsd,dv->bsv", x, p["unembed/w"], cast), targets)


def flops_per_token(c: dict, seq: int) -> float:
    """Forward FLOPs per token that the model requires: the layers'
    projections, the WKV state ops (read-out r.S and the update k v^T, per
    head hs x hs each) and the LM head; no embedding gather, no
    recomputation."""
    d, f, r = c["d_model"], c["d_ff"], c["decay_lora"]
    proj = 5 * d * d + 2 * d * r + 2 * d * f + d * d
    wkv_ops = 2 * c["n_heads"] * c["head_size"] ** 2
    return 2.0 * (c["n_layers"] * (proj + wkv_ops) + d * c["vocab_size"])
