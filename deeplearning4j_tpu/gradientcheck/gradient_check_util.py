"""Central finite-difference gradient checker.

Mirror of reference gradientcheck/GradientCheckUtil.java:48 (217 LoC):
perturb each parameter +-epsilon, compare the centered difference of the
score against the analytic gradient. In the reference the analytic side is
hand-written backprop; here it is ``jax.grad`` of the same jitted loss, so
the check validates loss/regularization/masking wiring rather than chain
rules — the same role it plays in the reference's test suite
(SURVEY.md §4 "Math/gradient correctness").

Double precision is enabled per-call via ``jax.enable_x64`` like the
reference's requirement that gradient checks run in double precision.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64


def check_gradients(
    net,
    ds,
    epsilon: float = 1e-6,
    max_rel_error: float = 1e-3,
    min_abs_error: float = 1e-8,
    max_params_to_check: Optional[int] = None,
    print_results: bool = False,
    seed: int = 0,
) -> bool:
    """True iff all (sampled) parameters pass the relative-error gate.

    rel_err = |analytic - numeric| / (|analytic| + |numeric|), skipped when
    both magnitudes are below ``min_abs_error`` — same gating as the
    reference's GradientCheckUtil.
    """
    from jax.flatten_util import ravel_pytree

    net.init()
    with enable_x64(True):
        params64 = jax.tree.map(
            lambda p: jnp.asarray(np.asarray(p), jnp.float64), net.params
        )
        state64 = jax.tree.map(
            lambda p: jnp.asarray(np.asarray(p), jnp.float64), net.state
        )
        feats = jnp.asarray(np.asarray(ds.features), jnp.float64)
        labels = jnp.asarray(np.asarray(ds.labels), jnp.float64)
        fm = (
            None
            if ds.features_mask is None
            else jnp.asarray(np.asarray(ds.features_mask), jnp.float64)
        )
        lm = (
            None
            if ds.labels_mask is None
            else jnp.asarray(np.asarray(ds.labels_mask), jnp.float64)
        )

        flat0, unravel = ravel_pytree(params64)

        def loss_flat(flat):
            params = unravel(flat)
            # Deterministic loss: no rng -> no dropout/sampling.
            score, _ = net._loss_fn(
                params, state64, None, feats, labels, fm, lm
            )
            return score

        loss_jit = jax.jit(loss_flat)
        analytic = np.asarray(jax.jit(jax.grad(loss_flat))(flat0))
        flat0 = np.asarray(flat0)

        n = flat0.shape[0]
        if max_params_to_check is not None and max_params_to_check < n:
            rng = np.random.default_rng(seed)
            idxs = rng.choice(n, size=max_params_to_check, replace=False)
        else:
            idxs = np.arange(n)

        n_pass = n_fail = 0
        max_err = 0.0
        for i in idxs:
            e = np.zeros_like(flat0)
            e[i] = epsilon
            s_plus = float(loss_jit(jnp.asarray(flat0 + e)))
            s_minus = float(loss_jit(jnp.asarray(flat0 - e)))
            numeric = (s_plus - s_minus) / (2.0 * epsilon)
            a = float(analytic[i])
            denom = abs(a) + abs(numeric)
            if denom < min_abs_error:
                n_pass += 1
                continue
            rel = abs(a - numeric) / denom
            max_err = max(max_err, rel)
            if rel > max_rel_error:
                n_fail += 1
                if print_results:
                    print(
                        f"param[{i}] FAIL rel={rel:.3e} "
                        f"analytic={a:.6e} numeric={numeric:.6e}"
                    )
            else:
                n_pass += 1
        if print_results:
            print(
                f"Gradient check: {n_pass} passed, {n_fail} failed, "
                f"max rel err {max_err:.3e}"
            )
        return n_fail == 0
