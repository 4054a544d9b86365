"""Compile a benchmark cell's programs at the real size for a DESCRIBED
v5e, without a chip (the on-chip-measurement guide's third rehearsal):
the net is built on the benchmark's normal path with
``ShapeDtypeStruct`` leaves in place of the seeded weights, and the
jitted programs the cell runs are lowered with the shapes its traffic
gives them. Prints each program's compile time, the compiler's memory
analysis, which custom calls it holds and the Pallas calls' instruction
names (a scope around a call can rename it: profiler/scopes.py). Nothing
runs: this says nothing about results or times.

    JAX_PLATFORMS=cpu python scripts/compile_cell.py --workload <cell> [program ...]

The programs by the configuration's ``model``: ``lfm2_moe`` (a training
cell): ``plain`` and ``remat``, ``fit_scan``'s one step without and with
a layer's recomputation (``--batch N`` for another batch); ``afmoe`` (a
served cell): ``decode`` and ``chunk``, the engine's two; ``cgpt_block``:
``decode`` (the served cell: the pool at the dtype the engine makes it,
``--pool-dtype`` for another) or ``step`` (the trained cell);
``granite_hybrid`` (a served cell): ``decode`` and ``prefill``, the cold
prefill at one prompt bucket (``--batch N`` for a bucket of N tokens);
``evabyte`` (a served cell): ``decode`` and ``chunk``, over its layers'
two pools each.

``--hash`` lowers only and prints the SHA-256 of each program's text,
the Pallas kernels' serialized bodies left out (two trees that print
the same hash run the same program around the same kernel calls);
``--package-root <dir>`` imports ``deeplearning4j_tpu`` from another
tree, the benchmark's files from this one.
"""
import argparse, hashlib, os, re, sys, time
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
if "--package-root" in sys.argv:             # before the package loads
    sys.path.insert(0, os.path.abspath(
        sys.argv[sys.argv.index("--package-root") + 1]))
import jax, jax.numpy as jnp, numpy as np
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from benchmark import common

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
jax.default_backend = lambda: "tpu"          # steer the auto rules
CALLS = ("gmm", "tgmm", "flash", "_paged_flash_attention",
         "tpu_custom_call")


def S(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype), sharding=one)


def key_struct():
    key = jax.eval_shape(lambda: jax.random.key(0))
    return jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one)


def struct_net(model, cfg, dtype, head: dict, optimizer=None):
    """The cell's net, its weights module's ``make_params`` swapped for
    one that hands out shapes."""
    W = model.weights

    def struct_params(seed, cfg):
        kinds = W.layer_kinds(cfg)
        d = cfg["hidden_size"]
        p = {"0": {"W": S((cfg["vocab_size"], d), dtype)},
             str(len(kinds) + 1): {n: S(s, dtype) for n, s in head.items()}}
        for i, kind in enumerate(kinds):
            p[str(i + 1)] = {n: S(s, dtype)
                             for n, s in W.layer_shapes(cfg, kind).items()}
        return p

    W.make_params = struct_params
    net = model.build_net(cfg, 1, optimizer)
    n_par = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(net.params))
    print("parameters", n_par, "GiB at 2 B", n_par * 2 / 2**30,
          "at 16 B", n_par * 16 / 2**30)
    return net


HASH_ONLY = "--hash" in sys.argv


def kernel_instructions(txt):
    """The Pallas calls' HLO instruction names without their numbers
    (what the benchmark's readers find a kernel by), with counts."""
    names = re.findall(
        r'%?([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', txt)
    out = {}
    for n in names:
        n = re.sub(r"\.\d+$", "", n)
        out[n] = out.get(n, 0) + 1
    return out


def report(name, lowered):
    if HASH_ONLY:
        # (a Mosaic kernel's serialized body names its source file's
        # path, which differs from tree to tree: taken out)
        text = re.sub(r'backend_config = "[^"]*"', "", lowered.as_text())
        print(name, "lowered", hashlib.sha256(text.encode()).hexdigest(),
              flush=True)
        return
    t0 = time.time()
    c = lowered.compile()
    m = c.memory_analysis()
    txt = c.as_text()
    if os.environ.get("COMPILE_CELL_DUMP"):     # the compiled text, kept
        with open(os.path.join(os.environ["COMPILE_CELL_DUMP"],
                               name.replace(" ", "_") + ".hlo"), "w") as f:
            f.write(txt)
    sizes = [x / 2**30 for x in (
        m.argument_size_in_bytes, m.output_size_in_bytes,
        m.temp_size_in_bytes, m.alias_size_in_bytes)]
    print(name, "compiled in %.1fs" % (time.time() - t0),
          "args %.2f GiB out %.2f temp %.2f alias %.2f" % tuple(sizes),
          "total %.2f" % (sizes[0] + sizes[1] + sizes[2] - sizes[3]),
          "custom calls:", {n: txt.count(n) for n in CALLS},
          "kernel instructions:", kernel_instructions(txt), flush=True)


def lfm2_moe(cfg, mix, model, which, batch):
    d = cfg["hidden_size"]
    for what in which or ["plain", "remat"]:
        net = struct_net(model, dict(cfg, remat=(what == "remat")),
                         "float32", {"norm_w": (d,)})
        net.updater_state = {
            si: ({"m": sub, "v": sub} if sub else {})
            for si, sub in net.params.items()}
        ids = S((1, batch or mix["batch"], mix["seq_len"]), "int32")
        report(f"{what} batch {ids.shape[1]}", net._train_steps_scan.lower(
            net.params, net.state, net.updater_state, 0, key_struct(),
            ids, ids, 1.0))


def afmoe(cfg, mix, model, which, batch):
    from deeplearning4j_tpu.serving import DecodeEngine

    dt, d = cfg["dtype"], cfg["hidden_size"]
    # the weights module takes a layer's feed-forward kind alone
    shapes = model.weights.layer_shapes
    model.weights.layer_shapes = lambda cfg, kind: shapes(cfg, kind[1])
    net = struct_net(model, cfg, dt,
                     {"norm_w": (d,), "E": (cfg["vocab_size"], d)})
    dep = dict(cfg["deployment"])
    dep.pop("why")
    eng = DecodeEngine(net, seed=1, **dep)
    print("kinds", [(k.window, k.layers, k.ring, k.pool.n_blocks)
                    for k in eng.kv.kinds])
    pool = {}
    for k in eng.kv.kinds:
        shp = (k.pool.n_blocks, eng.block_tokens,
               cfg["num_key_value_heads"], cfg["head_dim"])
        for name in k.layers:
            pool[name] = {"pk": S(shp, dt), "pv": S(shp, dt)}
    print("pool GiB", sum(int(np.prod(l.shape)) * 2
                          for l in jax.tree.leaves(pool)) / 2**30)
    rings = [k.ring for k in eng.kv.kinds]
    width = 2 * sum(rings) + len(rings) + 1
    B, c = eng.n_slots, eng.prefill_chunk
    which = which or ["decode", "chunk"]
    if "decode" in which:
        report("decode", eng._decode_jit.lower(
            eng._params, eng._state, pool, S((B, width), "int32"),
            S((B,), "int32"), S((B,), "float32"), S((B,), "int32"),
            key_struct(), S((B,), "int32")))
    if "chunk" in which:
        report("chunk_prefill", eng._chunk_jit.lower(
            eng._params, eng._state, S((1, c), "int32"),
            S((1, c), "float32"), pool, S((1, width), "int32"),
            S((1,), "float32"), S((1,), "int32"), key_struct()))


def evabyte(cfg, mix, model, which, batch):
    from deeplearning4j_tpu.serving import DecodeEngine

    dt, d = cfg["dtype"], cfg["hidden_size"]
    W = model.weights

    def struct_params(seed, cfg):
        n = cfg["num_hidden_layers"]
        v = cfg["vocab_size"]
        p = {"0": {"W": S((v, d), dt)},
             str(n + 1): {"norm_w": S((d,), dt),
                          "E": S((cfg["num_pred_heads"] * v, d), dt)}}
        for i in range(n):
            p[str(i + 1)] = {name: S(shape, dt) for name, shape
                             in W.layer_shapes(cfg).items()}
        return p

    W.make_params = struct_params
    net = model.build_net(cfg, 1)
    n_par = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(net.params))
    print("parameters", n_par, "GiB at 2 B", n_par * 2 / 2**30)
    dep = dict(cfg["deployment"])
    dep.pop("why")
    eng = DecodeEngine(net, seed=1, **dep)
    print("kinds", [(k.window, k.span, k.leaves, k.ring, k.slot_worst,
                     k.pool.n_blocks) for k in eng.kv.kinds])
    pool = {}
    for k in eng.kv.kinds:
        shp = (k.pool.n_blocks, eng.block_tokens,
               cfg["num_attention_heads"], W.head_dim(cfg))
        for name in k.layers:
            pool.setdefault(name, {}).update(
                {leaf: S(shp, dt) for leaf in k.leaves})
    print("pool GiB", sum(int(np.prod(l.shape)) * 2
                          for l in jax.tree.leaves(pool)) / 2**30)
    rings = [k.ring for k in eng.kv.kinds]
    width = 2 * sum(rings) + len(rings) + 1
    B, c = eng.n_slots, eng.prefill_chunk
    which = which or ["decode", "chunk"]
    if "decode" in which:
        report("decode", eng._decode_jit.lower(
            eng._params, eng._state, pool, S((B, width), "int32"),
            S((B,), "int32"), S((B,), "float32"), S((B,), "int32"),
            key_struct(), S((B,), "int32")))
    if "chunk" in which:
        report("chunk_prefill", eng._chunk_jit.lower(
            eng._params, eng._state, S((1, c), "int32"),
            S((1, c), "float32"), pool, S((1, width), "int32"),
            S((1,), "float32"), S((1,), "int32"), key_struct()))


def cgpt_block(cfg, mix, model, which, batch, pool_dtype=None):
    from deeplearning4j_tpu.serving import DecodeEngine

    W = model.weights
    make = W.make_params
    serve = "deployment" in cfg
    cd = cfg["compute_dtype"]

    def struct_params(*a):
        # a served net as the engine holds it: every layer but the head
        # at the compute dtype already (nothing to cast without arrays)
        tree = jax.eval_shape(lambda: make(*a))
        head = max(tree, key=int)
        return {k: {n: S(l.shape, l.dtype if not serve or k == head
                         else cd) for n, l in sub.items()}
                for k, sub in tree.items()}

    W.make_params = struct_params
    if not serve:
        # (the moments by hand: an updater's ``init`` would make arrays)
        net = model.build_net(cfg, 1)
        net.updater_state = {
            si: ({"m": sub, "v": sub} if sub else {})
            for si, sub in net.params.items()}
        b, t, v = batch or mix["batch"], mix["seq_len"], cfg["vocab_size"]
        x = S((1, b, v, t), "uint8")
        report(f"step batch {b}", net._train_steps_scan.lower(
            net.params, net.state, net.updater_state, 0, key_struct(),
            x, x, 1.0))
        return
    net = model.build_net(cfg, 1)
    dep = {k: v for k, v in cfg["deployment"].items() if k != "why"}
    eng = DecodeEngine(net, seed=1, **dep)
    held = pool_dtype or str(jnp.dtype(net._compute_dtype or net._dtype))
    h = cfg["n_head"]
    shp = (eng.kv_blocks, eng.block_tokens, h, cfg["n_embd"] // h)
    pool = {name: {"pk": S(shp, held), "pv": S(shp, held)}
            for k in eng.kv.kinds for name in k.layers}
    print("pool", held, "GiB", sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree.leaves(pool)) / 2**30)
    B, ring = eng.n_slots, eng.kv.kinds[0].ring
    report("decode", eng._decode_jit.lower(
        eng._params, eng._state, pool, S((B, 2 * ring + 2), "int32"),
        S((B,), "int32"), S((B,), "float32"), S((B,), "int32"),
        key_struct()))


def granite_hybrid(cfg, mix, model, which, batch):
    from deeplearning4j_tpu.serving import DecodeEngine

    dt, d = cfg["dtype"], cfg["hidden_size"]
    net = struct_net(model, cfg, dt, {"norm_w": (d,)})
    dep = {k: v for k, v in cfg["deployment"].items() if k != "why"}
    eng = DecodeEngine(net, seed=1, **dep)
    bucket = batch or 256
    row = (S((1, bucket), "int32"), S((1, bucket), "float32"),
           S((1,), "float32"), S((1,), "int32"), key_struct())
    which = which or ["decode", "prefill"]
    if "prefill" in which:
        report(f"prefill bucket {bucket}", eng._prefill_jit.lower(
            eng._params, eng._state, *row))
    if "decode" in which:
        # the pool as ``_ensure_pool`` makes it from a prefilled row:
        # KV leaves a block, the slot-state layers' rows a slot
        _, rnn1, _ = jax.eval_shape(eng._prefill_jit, eng._params,
                                    eng._state, *row)
        kv, slots = eng._split_row(rnn1)
        pool = {name: {leaf: S((eng.kv_blocks, eng.block_tokens,
                                st[leaf[1]].shape[1], st[leaf[1]].shape[3]),
                               dt) for leaf in ("pk", "pv")}
                for name, st in kv.items()}
        pool.update(jax.tree.map(
            lambda a: S((eng.n_slots,) + a.shape[1:], a.dtype), slots))
        B, ring = eng.n_slots, eng.kv.kinds[0].ring
        report("decode", eng._decode_jit.lower(
            eng._params, eng._state, pool, S((B, 2 * ring + 2), "int32"),
            S((B,), "int32"), S((B,), "float32"), S((B,), "int32"),
            key_struct(), S((B,), "int32")))


MODELS = {"lfm2_moe": lfm2_moe, "afmoe": afmoe, "cgpt_block": cgpt_block,
          "granite_hybrid": granite_hybrid, "evabyte": evabyte}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--pool-dtype", default=None)
    ap.add_argument("--hash", action="store_true")
    ap.add_argument("--package-root", default=ROOT)
    ap.add_argument("programs", nargs="*")
    args = ap.parse_args()
    cell, cfg, mix, model = common.find_cell(
        common.load_benchmark(), args.workload, False)
    if cfg["model"] not in MODELS:
        raise SystemExit(f"no programs listed for model {cfg['model']!r}: "
                         f"one of {sorted(MODELS)}")
    extra = ({"pool_dtype": args.pool_dtype} if args.pool_dtype else {})
    MODELS[cfg["model"]](cfg, mix, model, args.programs, args.batch,
                         **extra)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
