"""Batch statistics over the toy backbone (``toy_backbone.py``), standing in
for the program's own forward: ``AccumulationEngine(feature_fn=...)`` runs
it over each shard's packed tokens inside the engine's scan.  The traffic
file's ``dtype`` is the type the weights are served in."""
import jax
import jax.numpy as jnp

from bench.drivers.batch import Driver as Batch


def toy_features(params, tokens):
    """(rows, d) float32 pooled features of packed token rows; a row of
    padding (all ids 0) gives zeros."""
    real = tokens != 0
    count = jnp.maximum(jnp.sum(real, axis=1, keepdims=True), 1)
    x = params["embed"][tokens]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + params["eps"])
    x = x * params["norm"]
    x = x + jax.nn.gelu(x @ params["w_up"], approximate=False) @ params["w_down"]
    pooled = jnp.sum(jnp.where(real[..., None], x, 0), axis=1) / count.astype(x.dtype)
    return pooled.astype(jnp.float32)


class Driver(Batch):
    feature_fn = staticmethod(toy_features)

    def __init__(self, config, traffic, fed, mesh=None, seed=0):
        super().__init__(config, traffic, fed, mesh=mesh, seed=seed)
        model = config["backbone"]["model"]
        dtype = jnp.dtype(traffic.get("dtype", "float32"))
        params = {k: jnp.asarray(v, dtype) for k, v in fed.weights.items()}
        self.params = {**params, "eps": jnp.asarray(model["eps"], dtype)}
        self.flops += fed.backbone_module.flops(model, fed.lengths)
