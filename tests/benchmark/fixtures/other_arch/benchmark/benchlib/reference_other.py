"""The reference a configuration of another architecture brings, rehearsed
on the `tiny` program model: the weights and the forward pass are this
file's, the comparison, the padding rule and the child's contract are
`benchlib/reference.py`'s. (The block is the dense one, since that is what
`tiny` serves; the forward pass is written out layer by layer instead of a
scan, as a file of another architecture with unlike layers would.)"""

import sys

from benchlib.reference import _layer, _mm, init_weights, main, model_shapes, padded


class OtherReference:
    def __init__(self, cfg: dict, seed: int, pad_to: int = 0):
        self.s, self.pad_to = model_shapes(cfg), int(pad_to)
        self.weights = init_weights(cfg, seed)

    def logits(self, tokens: list, positions: list, low: bool = False):
        import jax
        import jax.numpy as jnp
        import numpy as np

        ids, pos = padded(tokens, positions, self.pad_to)
        layer, w = _layer(self.s, low), self.weights
        with jax.default_matmul_precision("highest"):
            x = w["embed"][ids].astype(jnp.float32)
            for i in range(self.s["layers"]):
                x = layer(x, jax.tree_util.tree_map(lambda leaf: leaf[i], w["layers"]))
            x = x[pos]
            x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.s["eps"])
            out = _mm(x * w["final_norm"].astype(jnp.float32), w["lm_head"], low)
        return np.asarray(out)[: len(positions)]


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], OtherReference))
