"""Gradient-check harness, analog of
``org.nd4j.autodiff.validation.GradCheckUtil`` / ``OpValidation`` and DL4J's
``org.deeplearning4j.gradientcheck.GradientCheckTests``.

Two modes:
- ``grad_check``  — central finite differences in float64 against
  ``jax.grad`` of a scalar-valued function over a pytree of inputs. This is
  the reference's exact methodology (central FD, double precision).
- ``check_vjp``   — stochastic VJP/JVP consistency via jax.test_util-style
  inner products, cheaper for large inputs.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np


def grad_check(fn: Callable, params, epsilon: float = 1e-5, max_rel_error: float = 1e-3,
               min_abs_error: float = 1e-8, subset: int = None, seed: int = 0) -> bool:
    """Central finite-difference check of ``jax.grad(fn)`` at ``params``.

    fn: pytree -> scalar. params: pytree of float arrays. Computation runs in
    float64 on CPU (enable_x64 scope) — matching the reference's
    double-precision gradcheck requirement.
    """
    with jax.enable_x64(True):
        params64 = jax.tree.map(lambda p: jnp.asarray(np.asarray(p), jnp.float64), params)
        analytic = jax.grad(fn)(params64)

        flat_p, treedef = jax.tree.flatten(params64)
        flat_g = jax.tree.leaves(analytic)
        rng = np.random.default_rng(seed)

        for leaf_idx, (p, g) in enumerate(zip(flat_p, flat_g)):
            p_np = np.asarray(p)
            n = p_np.size
            idxs = range(n) if subset is None or n <= subset else rng.choice(n, subset, replace=False)
            for i in idxs:
                orig = p_np.flat[i]

                def eval_at(v):
                    p_mod = p_np.copy()
                    p_mod.flat[i] = v
                    leaves = list(flat_p)
                    leaves[leaf_idx] = jnp.asarray(p_mod)
                    return float(fn(jax.tree.unflatten(treedef, leaves)))

                num = (eval_at(orig + epsilon) - eval_at(orig - epsilon)) / (2 * epsilon)
                ana = float(np.asarray(g).flat[i])
                abs_err = abs(num - ana)
                denom = max(abs(num), abs(ana))
                rel_err = abs_err / denom if denom > 0 else 0.0
                if abs_err > min_abs_error and rel_err > max_rel_error:
                    raise AssertionError(
                        f"Gradient check FAILED at leaf {leaf_idx} flat-index {i}: "
                        f"numerical={num:.8g} analytic={ana:.8g} relErr={rel_err:.3g}")
    return True


def check_vjp(fn: Callable, *primals, atol: float = 1e-4, rtol: float = 1e-4, eps: float = 1e-4) -> bool:
    """Cheap directional check: FD directional derivative vs JVP, plus
    VJP/JVP inner-product consistency <J v, u> == <v, J^T u>."""
    with jax.enable_x64(True):
        primals64 = jax.tree.map(lambda p: jnp.asarray(np.asarray(p), jnp.float64), primals)
        rng = np.random.default_rng(0)
        tangents = jax.tree.map(lambda p: jnp.asarray(rng.normal(size=p.shape)), primals64)
        y, jvp_out = jax.jvp(fn, primals64, tangents)
        cotangent = jax.tree.map(lambda o: jnp.asarray(rng.normal(size=o.shape)), y)
        _, vjp_fn = jax.vjp(fn, *primals64)
        vjp_out = vjp_fn(cotangent)

        # inner-product identity
        lhs = sum(float(jnp.vdot(a, b)) for a, b in zip(jax.tree.leaves(jvp_out), jax.tree.leaves(cotangent)))
        rhs = sum(float(jnp.vdot(a, b)) for a, b in zip(jax.tree.leaves(vjp_out), jax.tree.leaves(tangents)))
        np.testing.assert_allclose(lhs, rhs, atol=atol, rtol=rtol)

        # FD directional derivative
        def shift(t):
            return jax.tree.map(lambda p, d: p + t * d, list(primals64), list(tangents))
        y_plus = fn(*shift(eps))
        y_minus = fn(*shift(-eps))
        fd = jax.tree.map(lambda a, b: (a - b) / (2 * eps), y_plus, y_minus)
        for f, j in zip(jax.tree.leaves(fd), jax.tree.leaves(jvp_out)):
            np.testing.assert_allclose(np.asarray(f), np.asarray(j), atol=1e-3, rtol=1e-3)
    return True
