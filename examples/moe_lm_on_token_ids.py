"""Train an embedding-first mixture-of-experts language model on token
ids: ``lfm2_moe_lm`` (gated short-convolution and attention mixers,
dropless top-k experts, a head tied to the embedding) through
``fit_scan`` with INTEGER features and INTEGER labels. No one-hot of
the vocabulary is made on the host or the device: the ids go into the
embedding's gather and the loss is the log-softmax of the head's
float32 logits gathered at the label.

  python examples/moe_lm_on_token_ids.py

Runs on whatever platform JAX selects (DL4J_EXAMPLES_PLATFORM=cpu
forces the CPU).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

if os.environ.get("DL4J_EXAMPLES_PLATFORM", "native") == "cpu":
    jax.config.update("jax_platforms", "cpu")

import numpy as np

from deeplearning4j_tpu.datasets.markov import make_chain, sample_tokens
from deeplearning4j_tpu.models.zoo import lfm2_moe_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.listeners import TracingIterationListener
from deeplearning4j_tpu.profiler.tracer import Tracer

# DL4J_EXAMPLES_TINY=1: CI smoke mode (tests/test_examples_smoke.py)
TINY = os.environ.get("DL4J_EXAMPLES_TINY") == "1"
VOCAB, SEQ, BATCH, STEPS = (64, 32, 4, 8) if TINY else (256, 128, 8, 40)


def main():
    # a model's layers by its config's own keys: a dense conv layer,
    # then an attention and two conv layers over 8 experts, top 2; this
    # process holds experts 0-3 of each layer (``experts_held``), as
    # one chip of two would: picks on the others add nothing here, so
    # the routing is held out of learning (``freeze_router``), or the
    # router would teach itself to pick the experts this process holds
    conf = lfm2_moe_lm(
        vocab_size=VOCAB, hidden_size=64,
        layer_types=("conv", "full_attention", "conv", "conv"),
        num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=128, moe_intermediate_size=32, num_experts=8,
        num_experts_per_tok=2, experts_held=(0, 4), freeze_router=True,
        lr=3e-3, warmup_steps=4, total_steps=10 * STEPS)
    net = MultiLayerNetwork(conf).init()
    tracer = Tracer()
    net.set_listeners(TracingIterationListener(tracer, frequency=4))

    chain, _, floor = make_chain(VOCAB, seed=0)
    first = last = None
    for window in range(STEPS // 4):
        toks = sample_tokens(chain, 4 * BATCH, SEQ, seed=window).reshape(
            4, BATCH, SEQ + 1)
        # [K, B, T] int ids in, [K, B, T] int ids as labels
        scores = np.asarray(net.fit_scan(toks[:, :, :-1], toks[:, :, 1:]))
        first = scores[0] if first is None else first
        last = scores[-1]
    counters = tracer.latest_counters()
    print(f"loss {first:.3f} -> {last:.3f} over {net.iteration} steps "
          f"(ln V = {np.log(VOCAB):.3f}, the chain's floor {floor:.3f})")
    print("expert picks routed / on held experts:",
          int(counters["train_moe_picks"]), "/",
          int(counters["train_moe_picks_held"]))
    assert last < first


if __name__ == "__main__":
    main()
