"""The paged-attention kernel alone, at a serving configuration's geometry.

Builds one layer's KV pool, a block table for ``n_slots`` rows of which
``--live`` hold a context drawn from a traffic file's length
distributions (the rest are idle, scattered between the live ones), and
times three programs on the chip, ``--iters`` back-to-back calls each:

- ``kernel``: ``_paged_flash_attention`` alone (no scatter), a program
  a call: under ~0.22 ms a call this reads the host's time to enqueue
  one, not the device's;
- ``kernel_chain``: ``CHAIN`` (24, a decode step's layers) calls of it
  in ONE program, each call's output the next one's queries, so the
  device is the bound at any occupancy;
- ``attend_kernel`` / ``attend_gather``: ``AttentionImpl._paged_attend``
  through the kernel and through the XLA gather program it replaces,
  pool donated so the chunk's scatter is in place, as in the engine.

Per line: milliseconds a call, the steps the kernel pays for the tables
(grid steps plus the trips of its loop over a row's compute blocks,
``steps_paid``) beside those that score keys (``steps_scoring``), the
bytes of the live (mapped and reachable) pool blocks the call has to
read, and those bytes over the time as a share of the chip's HBM peak
(``benchmark/peaks.py``). ``--context N`` gives every live row N tokens
(``--live 48 --context 2047``: a full batch of full-window rows). The
geometry comes from a benchmark configuration file (heads, width,
window, ``deployment`` slots, block size and pool blocks); ``--heads``,
``--kv-heads`` and ``--window`` time the grouped form (a KV head's
query heads ride its tile) and a layer's own window; nothing here
is read by the benchmark.

    python scripts/paged_kernel_bench.py --live 5,24,36
    python scripts/paged_kernel_bench.py --live 48 --context 2047
    python scripts/paged_kernel_bench.py --heads 48 --kv-heads 8 --window 512
    python scripts/paged_kernel_bench.py --package-root _parent  # another tree

Fails off the TPU; ``--rehearse`` runs the configuration's rehearsal
geometry through the interpreter to check the script, and prints no
device number.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHAIN = 24      # kernel calls inside the kernel_chain program


def draw_contexts(rng, traffic: dict, n: int, cap: int) -> np.ndarray:
    """Live contexts of ``n`` requests caught mid-answer: a prompt from
    the mix's distribution plus a uniform share of a drawn answer."""
    def lognormal(spec):
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), spec["min"], spec["max"])

    ctx = lognormal(traffic["prompt"]) + np.floor(
        rng.random(n) * lognormal(traffic["output"]))
    return np.minimum(ctx, cap - 1).astype(np.int32)


def build_case(rng, geo: dict, contexts: np.ndarray, t: int):
    """Pool, tables and the kernel's operands for one call."""
    import jax.numpy as jnp

    b, h, dh = geo["n_slots"], geo["n_head"], geo["head_dim"]
    hk = geo["kv_heads"]       # the pool's heads; ``h`` query heads
    bt, nb, tm = geo["block_tokens"], geo["kv_blocks"], geo["window"]
    s_ring = 2 * -(-tm // bt) + 4
    filled = np.zeros(b, np.int32)
    rows = np.sort(rng.choice(b, len(contexts), replace=False))
    filled[rows] = contexts
    floor = np.zeros(b, np.int32)
    table = np.full((b, s_ring), -1, np.int32)
    base = np.full((b, s_ring), -1, np.int32)
    free = list(rng.permutation(nb))
    span = {r: range(max(0, int(filled[r]) - tm + 1) // bt,
                     (int(filled[r]) + t - 1) // bt + 1) for r in rows}
    live_blocks = sum(len(x) for x in span.values())
    # a case larger than the pool (a full batch of full-window rows)
    # keeps the blocks its chunk writes to itself and reads the rest
    # from a shared set: the same bytes a call, rows that alias
    written = {r: int(filled[r]) // bt for r in rows}
    shared = []
    if live_blocks > nb:
        own = sum(x.stop - written[r] for r, x in span.items())
        shared, free = free[:nb - own], free[nb - own:]
    for r in rows:
        for g in span[r]:
            if shared and g < written[r]:
                table[r, g % s_ring] = shared[(r * tm + g) % len(shared)]
            else:
                table[r, g % s_ring] = free.pop()
            base[r, g % s_ring] = g * bt
    ntab = min(s_ring, (tm + t - 2) // bt + 2)
    lo_blk = np.maximum(filled - tm + 1, 0) // bt
    g = lo_blk[:, None] + np.arange(ntab)[None, :]
    tb = np.take_along_axis(table, g % s_ring, axis=1)
    bb = np.take_along_axis(base, g % s_ring, axis=1)
    bval = (tb >= 0) & (bb == g * bt)
    lengths = np.where(filled > 0, t, 0).astype(np.int32)

    def draw(dtype, *shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)

    pool_dtype = jnp.dtype(geo["pool_dtype"])
    q_dtype = jnp.dtype(geo["compute_dtype"])
    return {
        "q": draw(q_dtype, b, h, t, dh), "k": draw(q_dtype, b, hk, t, dh),
        "v": draw(q_dtype, b, hk, t, dh),
        "pk": draw(pool_dtype, nb, bt, hk, dh),
        "pv": draw(pool_dtype, nb, bt, hk, dh),
        "table": jnp.asarray(table), "base": jnp.asarray(base),
        "floor": jnp.asarray(floor), "filled": jnp.asarray(filled),
        "bid": jnp.asarray(np.where(bval, tb, 0).astype(np.int32)),
        "bval": jnp.asarray(bval.astype(np.int32)),
        "lo_blk": jnp.asarray(lo_blk.astype(np.int32)),
        "lengths": jnp.asarray(lengths),
        "mask": jnp.asarray(
            (np.arange(t)[None] < lengths[:, None]).astype(np.float32)),
        "live_blocks": live_blocks, "ntab": ntab,
        "shared_blocks": bool(shared),
        "host_tables": (table, base, floor, filled),
        "block_bytes": 2 * bt * hk * dh * pool_dtype.itemsize,
    }


def walk_steps(att, geo: dict, case: dict, t: int) -> dict:
    """What the timed tree's kernel pays for the case's tables, by the
    tree's own count: the compute block's size, the steps a call pays
    and those that score keys. A tree from before the loop inside a
    grid step has no count of paid steps: its grid is a step a (row,
    query tile, compute block), computed here."""
    # (a tree since PR 35 sizes the compute block by the form too)
    form = ((geo["n_head"] // geo["kv_heads"], t)
            if hasattr(att, "_paged_tile_form") else ())
    per_step = att._paged_blocks_per_step(
        geo["block_tokens"], geo["kv_heads"], geo["head_dim"],
        geo["pool_dtype"], case["ntab"], *form)
    geometry = dict(block_tokens=geo["block_tokens"], window=geo["window"],
                    blocks_per_step=per_step, chunk=t)
    _, walked = att.paged_walk_counts(*case["host_tables"], **geometry)
    if hasattr(att, "paged_steps_paid"):
        paid = att.paged_steps_paid(*case["host_tables"], **geometry)
    else:
        paid = (geo["n_slots"] * (t // att._paged_q_tile(t))
                * -(-case["ntab"] // per_step))
    return {"blocks_per_step": per_step, "steps_paid": int(paid),
            "steps_scoring": walked // per_step}


def time_calls(fn, iters: int) -> float:
    """Milliseconds a call over ``iters`` back-to-back calls, after two
    that compile and warm; the device is the bound, the host only
    enqueues."""
    import jax

    for _ in range(2):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default=os.path.join(
        ROOT, "benchmark", "configs", "cgpt1p3b-serve.json"))
    ap.add_argument("--traffic", default=os.path.join(
        ROOT, "benchmark", "traffic", "chat-steady.json"))
    ap.add_argument("--live", default="24",
                    help="live rows, comma-separated for several cases")
    ap.add_argument("--context", type=int, default=None,
                    help="tokens every live row holds (default: drawn "
                         "from the traffic file's lengths)")
    ap.add_argument("--chunk", type=int, default=1,
                    help="query rows a call (1 = decode)")
    ap.add_argument("--pool-dtype", default=None,
                    help="the pool's cells (default: the configuration's "
                         "compute dtype, as the engine makes its pool)")
    ap.add_argument("--heads", type=int, default=None,
                    help="query heads (default: the configuration's)")
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="KV heads the pool holds, each serving heads / "
                         "kv-heads query heads (default: one a query "
                         "head); the width stays heads x head_dim")
    ap.add_argument("--window", type=int, default=None,
                    help="the layer's window (default: the "
                         "configuration's context)")
    ap.add_argument("--seed", type=int, default=25)
    ap.add_argument("--iters", type=int, default=48)
    ap.add_argument("--package-root", default=ROOT,
                    help="tree to import deeplearning4j_tpu from")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.package_root))
    sys.path.insert(1, ROOT)

    import jax

    from benchmark.peaks import peaks_of
    from deeplearning4j_tpu.nn.layers import attention as att

    dev = jax.devices()[0]
    print(json.dumps({"device_kind": dev.device_kind,
                      "platform": dev.platform,
                      "package_root": os.path.abspath(args.package_root)}))
    if dev.platform != "tpu" and not args.rehearse:
        print("paged_kernel_bench: no TPU; a time from another backend "
              "is not a device number (--rehearse checks the script)",
              file=sys.stderr)
        return 2
    with open(args.config) as f:
        conf = json.load(f)
    with open(args.traffic) as f:
        traffic = json.load(f)
    if args.rehearse:
        dep = dict(conf["deployment"], **conf["rehearsal"]["deployment"])
        conf = {**conf, **conf["rehearsal"], "deployment": dep}
        traffic = {**traffic, **traffic["rehearsal"]}
    dep = conf["deployment"]
    heads = args.heads or conf["n_head"]
    geo = {
        "n_slots": dep["n_slots"], "n_head": heads,
        "kv_heads": args.kv_heads or heads,
        "head_dim": conf["n_embd"] // conf["n_head"],
        "block_tokens": dep["block_tokens"], "kv_blocks": dep["kv_blocks"],
        "window": args.window or conf["n_positions"],
        "compute_dtype": conf["compute_dtype"],
        "pool_dtype": args.pool_dtype or conf["compute_dtype"],
    }
    toggle = "interpret" if args.rehearse else True
    iters = 2 if args.rehearse else args.iters
    n_chain = 2 if args.rehearse else CHAIN
    peak = None if args.rehearse else peaks_of(dev.device_kind)
    lc = att.MultiHeadSelfAttention(
        n_in=conf["n_embd"], n_out=conf["n_embd"], n_heads=heads,
        stream_max_t=geo["window"])
    for n_live in (int(x) for x in args.live.split(",")):
        rng = np.random.default_rng([args.seed, n_live])
        n_live = min(n_live, geo["n_slots"])
        ctx = draw_contexts(rng, traffic, n_live, geo["window"])
        if args.context is not None:
            ctx = np.full(n_live, args.context, np.int32)
        case = build_case(rng, geo, ctx, args.chunk)
        live_bytes = case["live_blocks"] * case["block_bytes"]
        steps = walk_steps(att, geo, case, args.chunk)

        kernel = jax.jit(lambda c: att._paged_flash_attention(
            c["q"], c["pk"], c["pv"], c["bid"], c["bval"], c["lo_blk"],
            c["floor"], c["filled"], c["lengths"], tm=geo["window"],
            interpret=toggle == "interpret"))
        ops = {k: case[k] for k in (
            "q", "pk", "pv", "bid", "bval", "lo_blk", "floor", "filled",
            "lengths")}
        chain = jax.jit(lambda c: jax.lax.scan(
            lambda q, _: (kernel(dict(c, q=q)), None), c["q"], None,
            length=n_chain)[0])
        programs = {"kernel": lambda: kernel(ops),
                    "kernel_chain": lambda: chain(ops)}

        def attend(flag):
            bean = dataclasses.replace(lc, use_flash_paged=flag)
            step = jax.jit(
                lambda pool, c: att.AttentionImpl._paged_attend(
                    bean, c["q"], c["k"], c["v"], dict(c, **pool),
                    c["mask"] if args.chunk > 1 else None),
                donate_argnums=0)
            rest = {k: case[k] for k in (
                "q", "k", "v", "table", "base", "floor", "filled", "mask")}
            state = {"pool": {"pk": case["pk"] + 0, "pv": case["pv"] + 0}}

            def call():
                o, cache = step(state["pool"], rest)
                state["pool"] = {"pk": cache["pk"], "pv": cache["pv"]}
                return o
            return call

        programs["attend_kernel"] = attend(toggle)
        programs["attend_gather"] = attend(False)
        for name, fn in programs.items():
            ms = time_calls(fn, iters)
            if name == "kernel_chain":
                ms /= n_chain
            line = {
                "program": name, "live_rows": n_live,
                "mean_context": round(float(ctx.mean()), 1) if n_live else 0,
                "chunk": args.chunk, "pool_dtype": geo["pool_dtype"],
                "heads": heads, "kv_heads": geo["kv_heads"],
                "window": geo["window"],
                "ntab": case["ntab"], "live_blocks": case["live_blocks"],
                "live_bytes": live_bytes,
            }
            if case["shared_blocks"]:
                line["shared_blocks"] = True
            line.update(steps)
            if args.rehearse:
                line["rehearsal"] = "interpreter; no device number"
            else:
                line["ms_per_call"] = round(ms, 4)
                line["hbm_peak_share_pct"] = round(
                    100 * live_bytes / (ms * 1e-3)
                    / peak["hbm_bytes_per_s"], 2)
            print(json.dumps(line), flush=True)
        # the two attend programs have scattered the same chunk into
        # equal pools, so their outputs may differ by rounding alone
        diff = np.abs(np.asarray(
            programs["attend_kernel"]().astype(np.float32))
            - np.asarray(programs["attend_gather"]().astype(np.float32)))
        print(json.dumps({"live_rows": n_live, "kernel_vs_gather_max_diff":
                          float(diff.max())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
