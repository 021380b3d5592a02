"""Serialization, checkpointing, helpers."""
from deeplearning4j_tpu.utils.serialization import ModelSerializer


def strengthen_dtypes(tree):
    """Strip jax weak_type from every leaf (lax.convert_element_type to the
    same dtype). Weak-typed leaves (e.g. ``jnp.full(shape, 0.0)`` biases)
    change signature after the first optimizer step — params go weak→strong
    — which silently RETRACES the whole-net jitted train step on the second
    and third calls (one full XLA compile each, ~14 s for ResNet-50).
    Strengthening at init makes step 1's signature identical to step N's."""
    import jax
    from jax import lax

    def fix(a):
        if hasattr(a, "dtype") and hasattr(a, "weak_type") and a.weak_type:
            return lax.convert_element_type(a, a.dtype)
        return a

    return jax.tree.map(fix, tree)
