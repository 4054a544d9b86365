"""Compile a benchmark cell's programs at the real size for a DESCRIBED
v5e, without a chip (the on-chip-measurement guide's third rehearsal):
the net is built on the benchmark's normal path with
``ShapeDtypeStruct`` leaves in place of the seeded weights, and the
jitted programs the cell runs are lowered with the shapes its traffic
gives them. Prints each program's compile time, the compiler's memory
analysis and which custom calls it holds. Nothing runs: this says
nothing about results or times.

    JAX_PLATFORMS=cpu python scripts/compile_cell.py --workload <cell> [program ...]

The programs by the configuration's ``model``: ``lfm2_moe`` (a training
cell): ``plain`` and ``remat``, ``fit_scan``'s one step without and with
a layer's recomputation (``--batch N`` for another batch); ``afmoe`` (a
served cell): ``decode`` and ``chunk``, the engine's two.
"""
import argparse, os, sys, time
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
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


def report(name, lowered):
    t0 = time.time()
    c = lowered.compile()
    m = c.memory_analysis()
    txt = c.as_text()
    sizes = [x / 2**30 for x in (
        m.argument_size_in_bytes, m.output_size_in_bytes,
        m.temp_size_in_bytes, m.alias_size_in_bytes)]
    print(name, "compiled in %.1fs" % (time.time() - t0),
          "args %.2f GiB out %.2f temp %.2f alias %.2f" % tuple(sizes),
          "total %.2f" % (sizes[0] + sizes[1] + sizes[2] - sizes[3]),
          "custom calls:", {n: txt.count(n) for n in CALLS}, flush=True)


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
                    for k in eng._kinds])
    pool = {}
    for k in eng._kinds:
        shp = (k.pool.n_blocks, eng.block_tokens,
               cfg["num_key_value_heads"], cfg["head_dim"])
        for name in k.layers:
            pool[name] = {"pk": S(shp, dt), "pv": S(shp, dt)}
    print("pool GiB", sum(int(np.prod(l.shape)) * 2
                          for l in jax.tree.leaves(pool)) / 2**30)
    rings = [k.ring for k in eng._kinds]
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


MODELS = {"lfm2_moe": lfm2_moe, "afmoe": afmoe}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("programs", nargs="*")
    args = ap.parse_args()
    cell, cfg, mix, model = common.find_cell(
        common.load_benchmark(), args.workload, False)
    if cfg["model"] not in MODELS:
        raise SystemExit(f"no programs listed for model {cfg['model']!r}: "
                         f"one of {sorted(MODELS)}")
    MODELS[cfg["model"]](cfg, mix, model, args.programs, args.batch)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
