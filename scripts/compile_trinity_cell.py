"""Compile the Trinity cell's two engine programs (``decode``,
``chunk_prefill``) at the real size for a DESCRIBED v5e, without a chip
(the on-chip-measurement guide's third rehearsal): the net is built on
the benchmark's normal path with ``ShapeDtypeStruct`` leaves in place of
the seeded weights, the engine is constructed as the cell constructs it,
and its jitted programs are lowered with the pool's and the tables'
shapes. Prints each program's compile time, the compiler's memory
analysis and how many paged-kernel and grouped-product calls it holds.
Nothing runs: this says nothing about results or times.

    JAX_PLATFORMS=cpu python scripts/compile_trinity_cell.py [decode] [chunk]
"""
import os, sys, time, json
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import jax, jax.numpy as jnp, numpy as np
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
jax.default_backend = lambda: "tpu"          # steer the auto rules

def S(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype), sharding=one)

from benchmark import common
bench = common.load_benchmark()
cell, cfg, mix, model = common.find_cell(bench, "trinity-large-serve.docs-mixed-tlp", False)
from benchmark.models import afmoe_weights as W
from deeplearning4j_tpu.serving import DecodeEngine

# the net with shapes in place of weights
orig = W.make_params
def struct_params(seed, cfg):
    kinds = W.layer_kinds(cfg)
    dt = cfg["dtype"]
    p = {"0": {"W": S((cfg["vocab_size"], cfg["hidden_size"]), dt)},
         str(len(kinds) + 1): {"norm_w": S((cfg["hidden_size"],), dt),
                               "E": S((cfg["vocab_size"], cfg["hidden_size"]), dt)}}
    for i, (_, ffn) in enumerate(kinds):
        p[str(i + 1)] = {n: S(s, dt) for n, s in W.layer_shapes(cfg, ffn).items()}
    return p
W.make_params = struct_params
net = model.build_net(cfg, 1)
n_par = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(net.params))
print("parameters", n_par, "GiB bf16", n_par * 2 / 2**30)
dep = dict(cfg["deployment"]); dep.pop("why")
eng = DecodeEngine(net, seed=1, **dep)
print("kinds", [(k.window, k.layers, k.ring, k.pool.n_blocks) for k in eng._kinds])
bt = eng.block_tokens
pool = {}
for k in eng._kinds:
    for name in k.layers:
        shp = (k.pool.n_blocks, bt, cfg["num_key_value_heads"], cfg["head_dim"])
        pool[name] = {"pk": S(shp, cfg["dtype"]), "pv": S(shp, cfg["dtype"])}
pool_bytes = sum(int(np.prod(l.shape)) * 2 for l in jax.tree.leaves(pool))
print("pool GiB", pool_bytes / 2**30)
rings = [k.ring for k in eng._kinds]
width = 2 * sum(rings) + len(rings) + 1
B = eng.n_slots
key = jax.eval_shape(lambda: jax.random.key(0))
key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one)
def report(name, lowered):
    t0 = time.time()
    c = lowered.compile()
    m = c.memory_analysis()
    txt = c.as_text()
    print(name, "compiled in %.1fs" % (time.time() - t0),
          "args %.2f GiB out %.2f temp %.2f alias %.2f" % tuple(
              x / 2**30 for x in (m.argument_size_in_bytes, m.output_size_in_bytes,
                                  m.temp_size_in_bytes, m.alias_size_in_bytes)),
          "paged kernel calls", txt.count("_paged_flash_attention"), "gmm", txt.count("gmm"),
          flush=True)
what = sys.argv[1:] or ["decode", "chunk"]
if "decode" in what:
    report("decode", eng._decode_jit.lower(
        eng._params, eng._state, pool, S((B, width), "int32"), S((B,), "int32"),
        S((B,), "float32"), S((B,), "int32"), key, S((B,), "int32")))
if "chunk" in what:
    c = eng.prefill_chunk
    report("chunk_prefill", eng._chunk_jit.lower(
        eng._params, eng._state, S((1, c), "int32"), S((1, c), "float32"), pool,
        S((1, width), "int32"), S((1,), "float32"), S((1,), "int32"), key))
