"""On the chip, at the cell's own size: the gaps of what the program
served against the reference computed WRONG in each of the layer's three
nearest mistakes, beside the gaps against the reference itself. A limit
is tight enough if each mistake lies past it."""
import json, os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # the repo root
import numpy as np
from benchmark import common, serve_cell
from benchmark.models import evabyte_reference as reference

bench = common.load_benchmark()
cell, cfg, mix, model = common.find_cell(bench, "evabyte-serve.docs-batch-eva", False)
common.setup_jax(cell, False)
seed = int(sys.argv[1]) if len(sys.argv) > 1 else 3000000303
from deeplearning4j_tpu.serving import DecodeEngine, Request
net = model.build_net(cfg, seed)
dep = dict(cfg["deployment"]); dep.pop("why")
eng = DecodeEngine(net, seed=seed & 0x7FFFFFFF, **dep)
rng = np.random.default_rng(seed)
prompts = [rng.integers(0, cfg["vocab_size"], n).tolist() for n in (12000, 6000)]
t0 = time.time()
ids = [eng.submit(Request(p, 320)) for p in prompts]
res = eng.run()
samples = [(p, list(res[i].tokens)) for p, i in zip(prompts, ids)]
print("served in %.1fs" % (time.time() - t0), flush=True)
del eng, net
common.free_device_memory()
out = {}
for broken in reference.BROKEN:
    prog = []
    for prompt, served in samples:
        seq = list(prompt) + list(served[:-1])
        toks = np.zeros((1, reference.padded(len(seq), cfg)), np.int32)
        toks[0, :len(seq)] = seq
        at = np.arange(len(prompt) - 1, len(seq))[None, :]
        ref = reference.logits_at(seed, cfg, toks, at, broken=broken)[0]
        prog.append(ref.max(axis=-1) - ref[np.arange(len(served)), np.asarray(served)])
    out[str(broken)] = serve_cell.gap_numbers(np.concatenate(prog))
    print(broken, out[str(broken)], "%.0fs" % (time.time() - t0), flush=True)
os.makedirs("chiprun_out/eva", exist_ok=True)
json.dump(out, open("chiprun_out/eva/broken.json", "w"))
