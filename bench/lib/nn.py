"""Plain float32 building blocks of the references.

Nothing here imports the program.  Every contraction runs at
``Precision.HIGHEST`` (a TPU otherwise multiplies float32 in one bfloat16
pass), and takes ``cast``, which is applied to both operands: the identity
for the reference, a rounding to a lower precision for its control.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def exact(x):
    return x


def e4m3(x):
    """Round to the values of float8 e4m3 (4 significant bits, normal down
    to 2^-6, subnormal steps of 2^-9, saturating at 448), computed in
    float32 so that it needs no float8 support from the backend."""
    x = jnp.clip(x, -448.0, 448.0)
    m, e = jnp.frexp(x)                       # x = m 2^e, 0.5 <= |m| < 1
    normal = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    return jnp.where(jnp.abs(x) >= 2.0 ** -6, normal,
                     jnp.round(x * 512.0) / 512.0)


def _scaled_e4m3(x):
    """e4m3 with one scale per tensor, its largest value mapped to 448, as
    float8 training scales its operands."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return e4m3(x / scale) * scale


@jax.custom_vjp
def fp8(x):
    """A contraction operand in float8: rounded in the forward pass, and
    its cotangent rounded in the backward pass."""
    return _scaled_e4m3(x)


fp8.defvjp(lambda x: (_scaled_e4m3(x), None),
           lambda _, g: (_scaled_e4m3(g),))


def dot(spec: str, a, b, cast=exact):
    return jnp.einsum(spec, cast(a), cast(b), precision=HI)


def layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def rms_norm(x, scale, eps=1e-6):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def shift(x):
    """x[t] -> x[t - 1], zeros at t = 0 (token shift)."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def cross_entropy(logits, targets):
    """Mean over every position of -log softmax(logits)[target]."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def scan_layers(layer, x, stacked: dict):
    """Apply ``layer(params_of_one_layer, x)`` over the leading layer axis,
    one layer's activations held at a time (each layer is recomputed in the
    backward pass)."""
    body = jax.checkpoint(lambda h, p: (layer(p, h), None))
    x, _ = jax.lax.scan(body, x, stacked)
    return x
