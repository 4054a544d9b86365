"""On-device token sampling for the batched decode step.

One jitted computation covers every slot's sampling config: greedy,
temperature, and top-k ride as PER-SLOT vectors (``temps[B]``,
``top_ks[B]``) so heterogeneous requests share the single compiled
decode step instead of forcing a retrace per config combination.

Also home of the speculative-decoding acceptance rules: given the
model's verify-pass outputs and a batch of right-padded drafts,
compute each slot's accepted-prefix length on device.
:func:`greedy_acceptance` is the equality rule (bit-parity with plain
greedy decode); :func:`stochastic_acceptance` is the rejection-sampling
rule (Leviathan et al.) that lets sampling-temperature traffic ride
the same verify pass, with :func:`residual_sample` emitting the
post-rejection correction token so accepted-token marginals match
target-model sampling exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Probability floor before the log: the output layer emits exact zeros
# for impossible classes under masking; log(0) would poison categorical.
_PROB_FLOOR = 1e-30


def _scaled_filtered_logits(probs, temps, top_ks):
    """Temperature-scaled, rank-top-k-filtered log-probabilities — the
    single definition of the sampling distribution ``p_tau`` every
    sampler entry point shares. Rank-based top-k (not value-threshold):
    ties at the k-th value would otherwise let MORE than k classes
    through, breaking the top_k=1 == greedy guarantee. Stable argsort
    breaks ties by class index — the same winner argmax picks.

    probs: [..., V]; temps/top_ks broadcast over the leading dims.
    Returns [..., V] logits with filtered classes at ``-inf``."""
    logits = jnp.log(jnp.maximum(probs, _PROB_FLOOR))
    order = jnp.argsort(-logits, axis=-1)
    ranks = jnp.argsort(order, axis=-1)
    filtered = jnp.where(ranks < top_ks[..., None], logits, -jnp.inf)
    return filtered / jnp.maximum(temps, 1e-6)[..., None]


def sample_tokens(probs, temps, top_ks, key):
    """Sample one token per slot from softmax row outputs.

    probs: [B, V] per-slot class probabilities (the RnnOutputLayer
    softmax at the last position).
    temps: [B] float — 0 means greedy; greedy rows take the SAME
    ``argmax(probs)`` the fused ``generate()`` path takes, so greedy
    engine output is bit-identical to ``generate()``.
    top_ks: [B] int32 — keep only each row's k highest-probability
    classes before sampling (V = unfiltered).
    key: PRNG key for this step.

    Returns int32 [B]. Dividing log-probabilities by the temperature
    differs from dividing logits only by a per-row constant, which
    ``jax.random.categorical`` is invariant to, and top-k on
    log-probabilities equals top-k on logits (monotone map)."""
    greedy = jnp.argmax(probs, axis=1).astype(jnp.int32)

    def draw():
        scaled = _scaled_filtered_logits(probs, temps, top_ks)
        sampled = jax.random.categorical(key, scaled, axis=-1).astype(
            jnp.int32)
        return jnp.where(temps > 0, sampled, greedy)

    # the filter's two sorts are a cost of every step: a batch whose
    # rows all decode greedily skips them (the ids are the same either
    # way)
    return jax.lax.cond(jnp.any(temps > 0), draw, lambda: greedy)


def greedy_acceptance(targets, draft, lens):
    """Accepted-prefix lengths for speculative verification under the
    GREEDY acceptance rule: draft token ``i`` is accepted iff it equals
    the model's argmax target at its position AND every earlier draft
    token was accepted (the leading-prefix reduction — one rejection
    invalidates everything after it, because later drafts were scored
    against a context containing the rejected token).

    targets: [B, W] int32 — argmax next-token id at each draft
    position (position ``i`` scores context + draft[:i]).
    draft: [B, W] int32, right-padded.
    lens: [B] int32 — valid draft length per row (pad never accepts).

    Returns int32 [B] accepted counts in ``[0, lens]``. Accepted
    tokens are by construction EXACTLY the tokens plain greedy decode
    would have emitted — the engine's bit-parity invariant rests on
    this equality, not on the draft's quality.

    Structured for future stochastic acceptance (Leviathan et al.'s
    p/q rejection sampling): swap the equality below for a per-position
    accept draw and keep the same cumulative-product prefix reduction.
    """
    w = draft.shape[1]
    pos = jnp.arange(w)
    ok = (draft == targets) & (pos[None, :] < lens[:, None])
    return jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                   axis=1).astype(jnp.int32)


def stochastic_acceptance(probs, draft, lens, temps, top_ks, key):
    """Accepted-prefix lengths under rejection-sampling acceptance
    (Leviathan et al. 2023): draft token ``i`` is accepted with
    probability ``min(1, p(x)/q(x))`` where ``p`` is the target
    sampling distribution and ``q`` the draft distribution. The n-gram
    drafter is DETERMINISTIC — ``q`` is a point mass on the drafted
    token — so the rule collapses to "accept with probability
    ``p_tau(draft_i)``", where ``p_tau`` is the temperature-scaled,
    top-k-filtered target distribution (the same one
    :func:`sample_tokens` draws from).

    probs: [B, W, V] — target softmax at each draft position
    (position ``i`` scores context + draft[:i]).
    draft: [B, W] int32, right-padded; lens: [B] valid lengths.
    temps/top_ks: [B] per-slot sampling config; greedy rows
    (``temps == 0``) keep the equality rule, so greedy acceptance —
    and with it the engine's greedy bit-parity invariant — is
    unchanged by this function existing.
    key: PRNG key for the per-position accept draws.

    Returns int32 [B] accepted counts in ``[0, lens]`` via the same
    cumulative-product leading-prefix reduction as
    :func:`greedy_acceptance` — one rejection invalidates everything
    after it. Together with :func:`residual_sample` at the first
    rejected position, emitted tokens are distributed EXACTLY as if
    the target model had sampled them one by one (the rejection-
    sampling identity: ``P[emit x] = p(x)·1 + (1-p(x))·p(x)/(1-p(x))``
    for a point-mass ``q``)."""
    b, w, _ = probs.shape
    greedy_ok = draft == jnp.argmax(probs, axis=-1).astype(jnp.int32)
    scaled = _scaled_filtered_logits(
        probs, jnp.broadcast_to(temps[:, None], (b, w)),
        jnp.broadcast_to(top_ks[:, None], (b, w)))
    p_tau = jax.nn.softmax(scaled, axis=-1)
    p_draft = jnp.take_along_axis(
        p_tau, draft[..., None].astype(jnp.int32), axis=-1)[..., 0]
    u = jax.random.uniform(key, (b, w))
    ok = jnp.where((temps > 0)[:, None], u < p_draft, greedy_ok)
    ok = ok & (jnp.arange(w)[None, :] < lens[:, None])
    return jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                   axis=1).astype(jnp.int32)


def residual_sample(probs, ban_tok, do_ban, temps, top_ks, key):
    """Bonus-token draw after a verify pass: like
    :func:`sample_tokens`, but rows with ``do_ban`` exclude
    ``ban_tok`` from the support (renormalized) — the residual
    distribution for a rejected point-mass draft. Masking happens
    AFTER the top-k rank filter: re-ranking after the ban would
    wrongly admit the (k+1)-th class into the support, which plain
    sampling could never emit.

    The all-``-inf`` row cannot occur: under ``top_k == 1`` the
    sampling distribution is a point mass on argmax, so a drafted
    argmax always accepts (``u < 1``) and a ban only ever fires on a
    non-argmax token, leaving argmax in support.

    probs: [B, V]; ban_tok: [B] int32; do_ban: [B] bool;
    temps/top_ks/key as in :func:`sample_tokens`. Returns int32 [B];
    greedy rows (``temps == 0``) return argmax regardless of the ban
    (a greedy rejection means the equality rule already failed — the
    model's own argmax IS the correction token)."""
    greedy = jnp.argmax(probs, axis=1).astype(jnp.int32)
    scaled = _scaled_filtered_logits(probs, temps, top_ks)
    v = probs.shape[-1]
    ban = do_ban[:, None] & (
        jnp.arange(v)[None, :] == ban_tok[:, None])
    scaled = jnp.where(ban, -jnp.inf, scaled)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(
        jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)
