"""A fixture model for ``test_bench_models.py``: the adapter of
``"model": "attn_lm"``, the program's bare causal-attention stack
(``models/zoo.py:transformer_lm``: ``depth`` layers of
``attn(x Wq, x Wk, x Wv) Wo + b`` with no residual, norm or
feed-forward, then a softmax head). The test copies it into a scratch
tree as ``benchmark/models/attn_lm.py``: its parameter tree (five
leaves a layer, two in the head) and its configuration keys (``width``,
``depth``, ``heads``, ``window``) are not the block's, and everything
it needs is in this one file.
"""

from __future__ import annotations

import functools
import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import norms, reference
from benchmark.reference import mm

LEAVES = ("Wq", "Wk", "Wv", "Wo", "b")

#: readers get the counts as ``obs["flops"]``; the toy has one
flops = types.SimpleNamespace(
    layer_matmul_params=lambda cfg: 4 * cfg["width"] ** 2)


def describe(cfg: dict) -> str:
    return (f"{cfg['depth']} bare attention layers of width "
            f"{cfg['width']} ({cfg['heads']} heads)")


# ---- weights from the seed, in the program's layout -------------------
def _layer(key, d_in: int, d: int) -> dict:
    shapes = {"Wq": (d_in, d), "Wk": (d_in, d), "Wv": (d_in, d),
              "Wo": (d, d), "b": (d,)}
    return {name: jax.random.normal(jax.random.fold_in(key, j),
                                    shapes[name], jnp.float32)
            * (0.1 if name == "b" else 1.0 / math.sqrt(shapes[name][0]))
            for j, name in enumerate(LEAVES)}


def start_params(seed: int, cfg: dict):
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    vocab, d, depth = cfg["vocab_size"], cfg["width"], cfg["depth"]
    for i in range(depth):
        yield {str(i): _layer(jax.random.fold_in(key, i),
                              vocab if i == 0 else d, d)}
    k = jax.random.fold_in(key, 1_000)
    yield {str(depth): {
        "W": jax.random.normal(k, (d, vocab), jnp.float32) / math.sqrt(d),
        "b": 0.1 * jax.random.normal(jax.random.fold_in(k, 1), (vocab,),
                                     jnp.float32)}}


def make_params(seed: int, cfg: dict) -> dict:
    return {k: v for part in start_params(seed, cfg)
            for k, v in part.items()}


# ---- the program's net -------------------------------------------------
def build_net(cfg: dict, seed: int, optimizer: dict = None):
    from deeplearning4j_tpu.models.zoo import transformer_lm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    opt = optimizer or {}
    conf = transformer_lm(
        n_in=cfg["vocab_size"], width=cfg["width"], n_layers=cfg["depth"],
        n_heads=cfg["heads"], n_classes=cfg["vocab_size"],
        lr=opt.get("learning_rate", 1e-3), seed=seed & 0x7FFFFFFF)
    for c in conf.confs:
        c.compute_dtype = cfg["compute_dtype"]
        if opt:
            c.lr_policy = "warmup_cosine"
        for key in ("lr_warmup_steps", "lr_total_steps", "lr_min_fraction",
                    "adam_mean_decay", "adam_var_decay", "epsilon"):
            if key in opt:
                setattr(c, key, opt[key])
        if hasattr(c.layer, "stream_max_t"):
            c.layer.stream_max_t = cfg["window"]
    net = MultiLayerNetwork(conf)
    net.params = make_params(seed, cfg)
    net.state = {}
    net.updater_state = {
        str(i): (upd.init(net.params[str(i)]) if optimizer else {})
        for i, upd in enumerate(net._updaters)}
    net._initialized = True
    return net


def encode_batch(tokens: np.ndarray, cfg: dict):
    eye = np.eye(cfg["vocab_size"], dtype=np.uint8)
    return (np.ascontiguousarray(eye[tokens[:, :-1]].transpose(0, 2, 1)),
            np.ascontiguousarray(eye[tokens[:, 1:]].transpose(0, 2, 1)))


# ---- the plain reference ----------------------------------------------
def _forward(params: dict, tokens, cfg: dict, prec: str):
    """Logits [N, T, V] over ``tokens`` [N, T]."""
    depth, h = cfg["depth"], cfg["heads"]
    x = None
    for i in range(depth):
        p = params[str(i)]
        if i == 0:     # one-hot times a matrix: its rows, rounded alike
            q, k, v = (reference._round_to(p[w], prec).astype(
                jnp.float32)[tokens] for w in ("Wq", "Wk", "Wv"))
        else:
            q, k, v = (mm(x, p[w], prec, "ntd,de->nte")
                       for w in ("Wq", "Wk", "Wv"))
        n, t, d = q.shape
        q, k, v = (a.reshape(n, t, h, d // h) for a in (q, k, v))
        s = mm(q, k, prec, "nqhc,nkhc->nhqk") / math.sqrt(d // h)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        o = mm(jax.nn.softmax(s, axis=-1), v, prec,
               "nhqk,nkhc->nqhc").reshape(n, t, d)
        x = mm(o, p["Wo"], prec, "ntd,de->nte") + p["b"]
    head = params[str(depth)]
    return mm(x, head["W"], prec, "ntd,dv->ntv") + head["b"]


def _row_loss(params, row, cfg, prec):
    logp = jax.nn.log_softmax(
        _forward(params, row[None, :-1], cfg, prec)[0], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, row[1:, None], axis=-1))


def train_reference(seed, cfg, hyper, batches, prec="highest") -> dict:
    out, params = reference.follow_steps(
        make_params(seed, cfg),
        functools.partial(_row_loss, cfg=cfg, prec=prec), hyper, batches)
    out["delta_norms"] = norms.flat_norms(
        norms.delta_norms(params, make_params(seed, cfg)))
    return out


def served_gaps(seed, cfg, samples, control=None):
    params = make_params(seed, cfg)
    return reference.served_gaps(
        lambda rows, prec: np.asarray(_forward(
            params, jnp.asarray(rows, jnp.int32), cfg, prec)),
        samples, control)
