"""Plain reference of a toy backbone, for the CPU tests of the harness's
backbone path: an embedding, one RMSNorm, one GELU MLP with a residual,
and a mean over each sample's real tokens.

``model``: ``vocab``, ``hidden`` (the pooled width d), ``ffn``, ``eps``.
Imports nothing of the program.
"""
import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import matmul

ROWS = 64  # samples per block of the reference's forward


def init(model: dict, seed: int) -> dict:
    """float32 weights from ``seed``, made on the device in one call."""
    V, d, f = model["vocab"], model["hidden"], model["ffn"]

    @jax.jit
    def make(key):
        k_embed, k_norm, k_up, k_down = jax.random.split(key, 4)
        return {
            "embed": jax.random.normal(k_embed, (V, d), jnp.float32),
            "norm": 1.0 + 0.1 * jax.random.normal(k_norm, (d,), jnp.float32),
            "w_up": jax.random.normal(k_up, (d, f), jnp.float32) / np.sqrt(d),
            "w_down": jax.random.normal(k_down, (f, d), jnp.float32) / np.sqrt(f),
        }

    return make(jax.random.key(seed))


def features(weights: dict, tokens, lengths, model: dict, precision: str = "highest"):
    """(rows, d) float32: the forward over ``tokens`` (rows, max), averaged
    over each row's first ``lengths`` positions."""
    x = weights["embed"][tokens]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + model["eps"])
    x = x * weights["norm"]
    h = jax.nn.gelu(matmul(x, weights["w_up"], precision), approximate=False)
    x = x + matmul(h, weights["w_down"], precision)
    real = jnp.arange(tokens.shape[1])[None, :] < lengths[:, None]
    return jnp.sum(jnp.where(real[..., None], x, 0.0), axis=1) / lengths[:, None]


def flops(model: dict, lengths) -> float:
    """The MLP's two products over the real tokens (2 FLOPs a multiply-add)."""
    return 2.0 * 2 * model["hidden"] * model["ffn"] * float(np.sum(lengths))


def bytes(model: dict, lengths) -> float:  # noqa: A001 - the module's contract names it
    """Read the MLP's and the norm's weights once, each real token's id and
    embedding row; write each sample's features."""
    d, f, n = model["hidden"], model["ffn"], len(lengths)
    tokens = float(np.sum(lengths))
    return 4.0 * (2 * d * f + d + tokens * (1 + d) + n * d)
