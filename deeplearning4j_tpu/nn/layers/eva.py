"""EVA attention (*Efficient Attention via Control Variates*,
arXiv:2302.04542, as the ``evabyte`` family runs it): exact keys inside
an ALIGNED window, one learned summary a chunk of every earlier window,
one softmax over both.

Per head ``h`` of width ``d``, scale ``s = d^-1/2``, ``q_i k_j v_j``
after the rotation, window ``W``, chunk ``C`` (``C | W``), learned
``mu_h, phi_h`` in ``R^d``. With ``k'_j = k_j d^-1/4``, every COMPLETE
chunk ``c = [cC, cC + C)`` is summarised once::

    a_cj = softmax_{j in c}(mu_h . k'_j)                  kt_c = sum_j a_cj k_j
    b_cj = softmax_{j in c}(phi_h . k'_j - |k'_j|^2 / 2)  vt_c = sum_j b_cj v_j

and query ``i`` of window ``w = i // W`` reads the exact keys
``E(i) = {j : wW <= j <= i}`` and the summaries ``S(i) = {c : c < wW/C}``
(those of every earlier window; a chunk of the current window is never
read as a summary)::

    o_i = sum_{E(i)} p_ij v_j + sum_{S(i)} pt_ic vt_c,
    (p_i, pt_i) = softmax over the joined logits [s q_i.k_j ; s q_i.kt_c]

with the scores, the softmax and the pooling in float32.

Three programs compute it, and ``nn/layers/hybrid.py`` picks by the
state a call is handed:

- :func:`full` (no state: ``output``, ``score``, a gradient): the whole
  sequence from position 0, a window of queries at a time (a
  ``W x W`` tile and a ``W x T/C`` one; a dense ``T x T`` matrix only
  where ``T <= W``), and the dense streaming state out.
- :func:`stream` (the net's own streaming state, ``rnn_time_step``):
  ``{"k", "v"}`` ``[N, H, W, d]``, the current window's keys by their
  offset in it, ``{"sk", "sv"}`` ``[N, H, S, d]``, the summaries by
  chunk, and ``"pos"`` ``[N]``. A chunk of ``t <= W`` tokens, masked or
  not, rows at any positions, a boundary anywhere inside it.
- :func:`paged` (the serving engine's block pools): the window's keys
  in one pool through one block table, the summaries in another through
  a second table whose blocks hold ``block_tokens`` ENTRIES each. The
  chunk's keys are written through the window table, every chunk the
  call completed is pooled from the blocks it lies in and written
  through the summary table, and the scores walk both tables: on the
  TPU two calls of the paged kernel (``_paged_flash_attention``: the
  window's causal form with the aligned floor, the summaries' form with
  no causal edge) that hand back their running maximum and sum, merged
  here; elsewhere the gather program, which is the kernel's oracle.
  The kernel calls need every query of a dispatch in ONE window: one
  token a row, or a chunk that ``W`` divides starting at a multiple of
  itself (the engine checks its ``prefill_chunk``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.layers import PagedCache
from deeplearning4j_tpu.nn.layers.attention import (
    _paged_flash_attention,
    _paged_table_entries,
    _should_use_flash_paged,
)
from deeplearning4j_tpu.profiler.scopes import scope

_NEG = -1e30


def summarize(k, v, mu, phi):
    """Pool chunks of keys and values to one entry each: ``k``, ``v``
    ``[..., C, H, d]`` (a chunk's tokens on axis -3), ``mu``, ``phi``
    ``[H, d]``. Float32 throughout; returns ``(kt, vt)``
    ``[..., H, d]`` float32."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    kp = kf * (kf.shape[-1] ** -0.25)
    a = jnp.sum(kp * mu.astype(jnp.float32), axis=-1)          # [.., C, H]
    b = (jnp.sum(kp * phi.astype(jnp.float32), axis=-1)
         - 0.5 * jnp.sum(kp * kp, axis=-1))
    a = jax.nn.softmax(a, axis=-2)[..., None]
    b = jax.nn.softmax(b, axis=-2)[..., None]
    return jnp.sum(a * kf, axis=-3), jnp.sum(b * vf, axis=-3)


def _joined(q, keys, vals, ok, skeys, svals, sok):
    """One softmax over exact keys and summaries: ``q`` ``[N, H, Q, d]``,
    ``keys`` / ``vals`` ``[N, H, K, d]`` under ``ok`` ``[N, Q, K]``,
    ``skeys`` / ``svals`` ``[N, H, S, d]`` under ``sok`` ``[N, Q, S]``;
    float32 scores and weights, the output at ``q``'s dtype. A query
    with nothing to read (a pad row) gets 0."""
    scale = q.shape[-1] ** -0.5
    s1 = jnp.einsum("nhqd,nhkd->nhqk", q, keys,
                    preferred_element_type=jnp.float32) * scale
    s2 = jnp.einsum("nhqd,nhsd->nhqs", q, skeys,
                    preferred_element_type=jnp.float32) * scale
    s = jnp.concatenate([jnp.where(ok[:, None], s1, _NEG),
                         jnp.where(sok[:, None], s2, _NEG)], axis=-1)
    seen = jnp.concatenate([ok, sok], axis=-1)[:, None]
    p = jnp.where(seen, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)),
                  0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    nk = keys.shape[2]
    # (values a query may not read are zeroed: a recycled block's NaN
    # survives a zero weight)
    vals = jnp.where(jnp.any(ok, axis=1)[:, None, :, None], vals, 0)
    svals = jnp.where(jnp.any(sok, axis=1)[:, None, :, None], svals, 0)
    o = (jnp.einsum("nhqk,nhkd->nhqd", p[..., :nk],
                    vals.astype(jnp.float32))
         + jnp.einsum("nhqs,nhsd->nhqd", p[..., nk:],
                      svals.astype(jnp.float32)))
    return o.astype(q.dtype)


def _chunks(x, chunk: int):
    """``[N, H, T, d]`` -> ``[N, T / C, C, H, d]`` (``C | T``)."""
    n, h, t, d = x.shape
    return jnp.transpose(x.reshape(n, h, t // chunk, chunk, d),
                         (0, 2, 3, 1, 4))


def _pooled(k, v, mu, phi, chunk: int):
    """Every chunk of ``k``, ``v`` ``[N, H, T, d]`` pooled:
    ``[N, H, T / C, d]`` float32 each."""
    kt, vt = summarize(_chunks(k, chunk), _chunks(v, chunk), mu, phi)
    return jnp.swapaxes(kt, 1, 2), jnp.swapaxes(vt, 1, 2)


# ---------------------------------------------------------------------
# the whole sequence at once
# ---------------------------------------------------------------------
def full(q, k, v, mu, phi, *, window: int, chunk: int, capacity: int,
         mask=None, want_state: bool = True):
    """``q``, ``k``, ``v`` ``[N, H, T, d]`` from position 0 (``mask``
    ``[N, T]``: each row's valid prefix). Returns ``(o, state)``,
    ``state`` the dense streaming state of :func:`stream` (None unless
    ``want_state``) with room for ``capacity`` tokens' summaries."""
    n, h, t, d = q.shape
    with scope("eva/window"):
        blk = window if t > window else -(-t // chunk) * chunk
        tp = -(-t // blk) * blk
        pad = ((0, 0), (0, 0), (0, tp - t), (0, 0))
        qp, kp, vp = (jnp.pad(a, pad) for a in (q, k, v))
        nw = tp // blk
    with scope("eva/write"):
        kt, vt = _pooled(kp, vp, mu, phi, chunk)          # [N, H, tp/C, d]
    with scope("eva/window"):
        i = jnp.arange(blk)
        ok = jnp.broadcast_to((i[None, :] <= i[:, None])[None],
                              (n, blk, blk))
        c = jnp.arange(tp // chunk)

        def one(w):
            sl = [jax.lax.dynamic_slice_in_dim(a, w * blk, blk, axis=2)
                  for a in (qp, kp, vp)]
            sok = jnp.broadcast_to(
                (c < w * (blk // chunk))[None, None, :],
                (n, blk, c.shape[0]))
            return _joined(*sl, ok, kt, vt, sok)

        if nw == 1:
            o = one(0)
        else:
            o = jax.lax.map(one, jnp.arange(nw))          # [nw, N, H, blk, d]
            o = jnp.moveaxis(o, 0, 2).reshape(n, h, tp, d)
        o = o[:, :, :t]
    if not want_state:
        return o, None
    with scope("eva/write"):
        pos = (jnp.full((n,), t, jnp.int32) if mask is None
               else jnp.sum(mask.astype(jnp.int32), axis=1))
        # the current window's keys by their offset in it
        at = (pos // window * window)[:, None] + jnp.arange(window)
        take = jnp.minimum(at, tp - 1)[:, None, :, None]
        live = (at < pos[:, None])[:, None, :, None]
        ck = jnp.where(live, jnp.take_along_axis(kp, take, axis=2), 0)
        cv = jnp.where(live, jnp.take_along_axis(vp, take, axis=2), 0)
        # the summaries of the chunks the row has completed
        n_s = capacity // chunk
        width = min(n_s, kt.shape[2])
        done = (jnp.arange(width)[None, :]
                < (pos // chunk)[:, None])[:, None, :, None]
        grow = ((0, 0), (0, 0), (0, n_s - width), (0, 0))
        sk = jnp.pad(jnp.where(done, kt[:, :, :width], 0), grow)
        sv = jnp.pad(jnp.where(done, vt[:, :, :width], 0), grow)
        return o, {"k": ck, "v": cv, "sk": sk.astype(k.dtype),
                   "sv": sv.astype(v.dtype), "pos": pos}


# ---------------------------------------------------------------------
# the net's own streaming state
# ---------------------------------------------------------------------
def stream(q, k, v, state, mu, phi, *, window: int, chunk: int,
           mask=None):
    """A chunk of ``t <= W`` tokens continuing ``state`` (the module
    docstring has its leaves). The current window's cache and the chunk
    are laid out by POSITION in one buffer of ``2 W`` slots, slot ``j``
    holding position ``w0 W + j`` (``w0`` the window the chunk starts
    in), so a boundary inside the chunk is a mask and nothing else."""
    n, h, t, d = q.shape
    if t > window:
        raise ValueError(
            f"rnn_time_step continuation chunk of {t} steps exceeds "
            f"eva_window={window}: stream smaller chunks")
    with scope("eva/write"):
        pos = state["pos"]
        lengths = (jnp.full((n,), t, jnp.int32) if mask is None
                   else jnp.sum(mask.astype(jnp.int32), axis=1))
        off = pos % window
        first = pos // window * (window // chunk)   # the buffer's chunk 0

        def lay(cache, new):
            buf = jnp.concatenate([cache, jnp.zeros_like(cache)], axis=2)
            return jax.vmap(
                lambda b, c, o: jax.lax.dynamic_update_slice_in_dim(
                    b, c.astype(b.dtype), o, axis=1))(buf, new, off)

        bk, bv = lay(state["k"], k), lay(state["v"], v)     # [N, H, 2W, d]
        end = off + lengths                                 # [N]
        j = jnp.arange(2 * window)
        # every chunk of the buffer that is now complete is pooled and
        # (re)written: one it completed before comes out the same
        kt, vt = _pooled(bk, bv, mu, phi, chunk)            # [N, H, 2W/C, d]
        m = jnp.arange(2 * window // chunk)
        n_s = state["sk"].shape[2]
        to = jnp.where((m[None, :] + 1) * chunk <= end[:, None],
                       first[:, None] + m[None, :], n_s)    # [N, 2W/C]

        def put(s, new, idx):
            return s.at[:, idx].set(new.astype(s.dtype), mode="drop")

        sk = jax.vmap(put)(state["sk"], kt, to)
        sv = jax.vmap(put)(state["sv"], vt, to)
    with scope("eva/window"):
        jq = off[:, None] + jnp.arange(t)[None, :]          # [N, t]
        ok = ((j[None, None, :] <= jq[:, :, None])
              & (j[None, None, :] >= (jq // window * window)[:, :, None])
              & (j[None, None, :] < end[:, None, None]))
    with scope("eva/summaries"):
        seen = ((pos[:, None] + jnp.arange(t)[None, :]) // window
                * (window // chunk))                        # [N, t]
        sok = jnp.arange(n_s)[None, None, :] < seen[:, :, None]
    with scope("eva/window"):
        o = _joined(q, bk, bv, ok, sk, sv, sok)
    with scope("eva/write"):
        new_pos = pos + lengths
        shift = (new_pos // window - pos // window) * window

        def keep(b, s):
            return jax.lax.dynamic_slice_in_dim(b, s, window, axis=1)

        return o, {"k": jax.vmap(keep)(bk, shift),
                   "v": jax.vmap(keep)(bv, shift),
                   "sk": sk, "sv": sv, "pos": new_pos}


# ---------------------------------------------------------------------
# the serving engine's block pools
# ---------------------------------------------------------------------
def visible(pos, window: int, chunk: int):
    """Summaries a query at ``pos`` reads: those of every window before
    its own."""
    return pos // window * (window // chunk)


def paged_caches(*, window: int, chunk: int, longest: int,
                 token_width: int):
    """The two caches an EVA layer holds in a served engine: its
    summaries (one entry a ``chunk`` of tokens, up to the longest
    context: never released while the row lives) and its window's exact
    keys (aligned)."""
    if longest <= window:
        raise ValueError(
            f"stream_max_t {longest} (the longest context) must pass "
            f"eva_window {window}")
    reads = functools.partial(_reads, window=window, chunk=chunk)
    return (
        PagedCache(longest, entry_tokens=chunk, token_width=token_width,
                   leaves=("sk", "sv"), operands=("stable", "sbase"),
                   reads=functools.partial(reads, "summary")),
        PagedCache(window, aligned=True, token_width=token_width,
                   name="eva_window",
                   reads=functools.partial(reads, "window")))


def _reads(which: str, lengths, *, window: int, chunk: int,
           queries: int = 1, tokens=None, steps: int = 1):
    """What ONE layer's attention reads of one cache for a dispatch
    whose rows hold ``lengths`` tokens (host numpy): the entries (the
    window's exact keys from each row's aligned floor up, or the
    summaries of the windows before it) and the (query, entry) pairs it
    scores. A decode dispatch (``queries`` 1) is ``steps`` steps, each a
    position on, a query a row. An admission's chunk of ``tokens``
    queries (it never straddles a window's end) reads each entry once
    and scores every pair under the causal edge."""
    n = 1 if queries == 1 else queries if tokens is None else tokens
    pos = (np.asarray(lengths, np.int64)[:, None]
           + np.arange(steps if queries == 1 else 1))
    if which == "window":
        read = pos % window + n
        pairs = n * (pos % window) + n * (n + 1) // 2
    else:
        read = visible(pos, window, chunk)
        pairs = n * read
    return {f"eva_{which}_entries_read": int(np.sum(read)),
            f"eva_{which}_pairs_scored": int(np.sum(pairs))}


#: the keys a served engine's stats hold for an EVA layer's caches, at
#: zero (whatever the net, so that a reader finds them): the summaries
#: the programs wrote and what ``_reads`` counts, each also under
#: ``prefill_``, and the window's blocks (``PagedCache.name``)
STATS = {
    **{prefix + name: 0 for prefix in ("", "prefill_")
       for name in ("eva_summaries_written",
                    "eva_window_entries_read", "eva_summary_entries_read",
                    "eva_window_pairs_scored", "eva_summary_pairs_scored")},
    "eva_window_blocks_allocated": 0, "eva_window_blocks_released": 0}


def paged(q, k, v, cache, mu, phi, *, window: int, chunk: int,
          toggle=None, mask=None):
    """The chunk's queries over the two pools (the module docstring).
    ``cache``: ``pk`` / ``pv`` ``[nb, bt, H, d]`` with ``table`` /
    ``base`` ``[B, S]``, ``floor``, ``filled`` ``[B]`` as
    ``AttentionImpl._paged_attend`` has them; ``sk`` / ``sv``
    ``[nb_s, bt, H, d]`` with ``stable`` / ``sbase`` ``[B, S_s]``, a
    summary block holding entries ``[g bt, (g + 1) bt)`` and ``sbase``
    the first TOKEN its chunks cover, ``g bt C``. Returns ``(o, new
    cache, written)``: ``filled`` advanced, and the summary entries this
    call wrote (an int32 scalar)."""
    b, h, t, d = q.shape
    pk, pv, sk, sv = cache["pk"], cache["pv"], cache["sk"], cache["sv"]
    table, base = cache["table"], cache["base"]
    stable, sbase = cache["stable"], cache["sbase"]
    floor, filled = cache["floor"], cache["filled"]
    nb, bt = pk.shape[0], pk.shape[1]
    nbs = sk.shape[0]
    if bt % chunk:
        raise ValueError(
            f"block_tokens {bt} is not a multiple of eva_chunk {chunk}: "
            "a chunk must lie inside one pool block")
    n_tok, n_ent = nb * bt, nbs * bt
    s_ring, ss_ring = table.shape[1], stable.shape[1]
    pkf, pvf = pk.reshape(n_tok, h, d), pv.reshape(n_tok, h, d)
    skf, svf = sk.reshape(n_ent, h, d), sv.reshape(n_ent, h, d)
    # -- the chunk's keys and values to their positions ----------------
    with scope("tables"):
        lengths = (jnp.full((b,), t, jnp.int32) if mask is None
                   else jnp.sum(mask.astype(jnp.int32), axis=1))
        pos = filled[:, None] + jnp.arange(t)[None, :]           # [B, t]
        blk = jnp.take_along_axis(table, (pos // bt) % s_ring, axis=1)
        writable = (jnp.arange(t)[None, :] < lengths[:, None]) & (blk >= 0)
        widx = jnp.where(writable, blk * bt + pos % bt, n_tok)
    with scope("eva/write"):
        kt_ = jnp.swapaxes(k, 1, 2).reshape(b * t, h, d)
        vt_ = jnp.swapaxes(v, 1, 2).reshape(b * t, h, d)
        pkf = pkf.at[widx.reshape(-1)].set(kt_.astype(pkf.dtype),
                                           mode="drop")
        pvf = pvf.at[widx.reshape(-1)].set(vt_.astype(pvf.dtype),
                                           mode="drop")
    # -- the chunks this call completed, pooled from their blocks ------
    with scope("tables"):
        written = filled + lengths
        ncand = (t + chunk - 2) // chunk + 1
        cand = (filled // chunk)[:, None] + jnp.arange(ncand)[None, :]
        tok = cand[:, :, None] * chunk + jnp.arange(chunk)   # [B, nc, C]
        tblk = jnp.take_along_axis(
            table, ((tok // bt) % s_ring).reshape(b, -1),
            axis=1).reshape(tok.shape)
        done = (((cand + 1) * chunk <= written[:, None])
                & ((cand + 1) * chunk > filled[:, None])
                & (tblk[:, :, 0] >= 0))
        ridx = jnp.where(tblk >= 0, tblk * bt + tok % bt, 0)
        sblk = jnp.take_along_axis(stable, (cand // bt) % ss_ring, axis=1)
        sidx = jnp.where(done & (sblk >= 0), sblk * bt + cand % bt, n_ent)
    with scope("eva/write"):
        kt, vt = summarize(pkf[ridx], pvf[ridx], mu, phi)   # [B, nc, H, d]
        skf = skf.at[sidx.reshape(-1)].set(
            kt.reshape(-1, h, d).astype(skf.dtype), mode="drop")
        svf = svf.at[sidx.reshape(-1)].set(
            vt.reshape(-1, h, d).astype(svf.dtype), mode="drop")
        n_written = jnp.sum((sidx < n_ent).astype(jnp.int32))
    # -- what the queries read -----------------------------------------
    ntab = _paged_table_entries(s_ring, window, bt, t)
    with scope("tables"):
        start = filled // window * window       # the first query's floor
        lo_blk = jnp.maximum(floor, start) // bt
        g = lo_blk[:, None] + jnp.arange(ntab)[None, :]          # [B, ntab]
        tb = jnp.take_along_axis(table, g % s_ring, axis=1)
        bb = jnp.take_along_axis(base, g % s_ring, axis=1)
        bval = (tb >= 0) & (bb == g * bt)
        gs = jnp.broadcast_to(jnp.arange(ss_ring)[None, :], (b, ss_ring))
        bvals = (stable >= 0) & (sbase == gs * (bt * chunk))
        n_vis = visible(filled, window, chunk)
    new = {"table": table, "base": base, "floor": floor,
           "filled": written, "stable": stable, "sbase": sbase}

    def pools():
        return {"pk": pkf.reshape(nb, bt, h, d),
                "pv": pvf.reshape(nb, bt, h, d),
                "sk": skf.reshape(nbs, bt, h, d),
                "sv": svf.reshape(nbs, bt, h, d)}

    if _should_use_flash_paged(toggle, bt, d, t):
        interpret = toggle == "interpret"
        new.update(pools())
        with scope("tables"):
            i32 = jnp.int32
            zero = jnp.zeros((b,), i32)
            win = (jnp.where(bval, tb, 0).astype(i32), bval.astype(i32),
                   lo_blk.astype(i32),
                   jnp.maximum(floor, start).astype(i32),
                   filled.astype(i32), lengths.astype(i32))
            summ = (jnp.where(bvals, stable, 0).astype(i32),
                    bvals.astype(i32), zero, zero, n_vis.astype(i32),
                    zero)
        with scope("eva/window"):
            o1, lse1 = _paged_flash_attention(
                q, new["pk"], new["pv"], *win, tm=window, stats=True,
                interpret=interpret)
        with scope("eva/summaries"):
            o2, lse2 = _paged_flash_attention(
                q, new["sk"], new["sv"], *summ, tm=ss_ring * bt,
                causal=False, stats=True, interpret=interpret)
            top = jnp.maximum(lse1, lse2)
            w1 = jnp.exp(lse1 - top)[..., None]
            w2 = jnp.exp(lse2 - top)[..., None]
            o = ((o1 * w1 + o2 * w2) / (w1 + w2)).astype(q.dtype)
        return o, new, n_written
    # the gather program: the kernel's semantics, its off-TPU path and
    # its oracle, a boundary anywhere inside the chunk
    with scope("tables"):
        off = jnp.arange(bt)
        gidx = (jnp.where(bval, tb, 0)[:, :, None] * bt
                + off).reshape(b, ntab * bt)
        kpos = (g[:, :, None] * bt + off).reshape(b, ntab * bt)
        kval = jnp.repeat(bval, bt, axis=1) & (
            kpos < written[:, None]) & (kpos >= floor[:, None])
        ok = (kval[:, None, :] & (kpos[:, None, :] <= pos[:, :, None])
              & (kpos[:, None, :]
                 >= (pos // window * window)[:, :, None]))
        sgidx = (jnp.where(bvals, stable, 0)[:, :, None] * bt
                 + off).reshape(b, ss_ring * bt)
        spos = (gs[:, :, None] * bt + off).reshape(b, ss_ring * bt)
        sok = (jnp.repeat(bvals, bt, axis=1)[:, None, :]
               & (spos[:, None, :]
                  < visible(pos, window, chunk)[:, :, None]))
    with scope("eva/window"):
        ek = jnp.swapaxes(pkf[gidx], 1, 2)                  # [B, H, K, d]
        ev = jnp.swapaxes(pvf[gidx], 1, 2)
    with scope("eva/summaries"):
        esk = jnp.swapaxes(skf[sgidx], 1, 2)
        esv = jnp.swapaxes(svf[sgidx], 1, 2)
        o = _joined(q, ek, ev, ok, esk, esv, sok)
    new.update(pools())
    return o, new, n_written
