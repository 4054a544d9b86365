"""Single-file model serialization.

TPU-native counterpart of the reference checkpoint triple — (conf JSON,
flat params, serialized updater) — written by
earlystopping/saver/LocalFileModelSaver.java:76-86 and restored via the
``MultiLayerNetwork(String conf, INDArray params)`` ctor
(nn/multilayer/MultiLayerNetwork.java:107). Here the triple is packed into
ONE zip archive so a model travels as a single artifact:

    model.zip
    ├── type                conf-class marker (multilayer | graph)
    ├── conf.json           configuration (the wire format, SURVEY.md §5.6)
    ├── params.npz          param pytree, keys "layer␟name" flattened
    └── extras.pkl          updater state + layer state + iteration

Arrays go through numpy ``.npz`` (portable, no pickle needed for params);
only updater/layer state uses pickle because its pytree structure is
heterogeneous.

This module is the SINGLE serialization implementation: network
``save/load`` methods and the CheckpointManager both delegate here
(``snapshot``/``write_snapshot`` split the host-copy step from the disk
write so async checkpointing can snapshot on the training thread and
write on a background one).
"""

from __future__ import annotations

import io
import os
import pickle
import zipfile
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

_SEP = "␟"  # unit-separator-ish key joiner, never in param names


def _flatten(tree: Dict[str, Any], prefix: str = "") -> Dict[str, np.ndarray]:
    out = {}
    for k, v in tree.items():
        key = f"{prefix}{_SEP}{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(_flatten(v, key))
        else:
            out[key] = np.asarray(v)
    return out


def _unflatten(flat: Dict[str, np.ndarray]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for key, v in flat.items():
        parts = key.split(_SEP)
        node = out
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jnp.asarray(v)
    return out


def _merge_into(dst: Dict[str, Any], src: Dict[str, Any]) -> Dict[str, Any]:
    """Overlay loaded leaves onto a freshly-init'd tree. Param-less layers
    (e.g. Subsampling) have empty dicts that npz flattening drops; merging
    keeps their keys so the forward pass still finds every layer."""
    out = dict(dst)
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge_into(out[k], v)
        else:
            out[k] = v
    return out


def snapshot(net) -> Dict[str, Any]:
    """Host-side copy of everything needed to reconstruct ``net``.
    Cheap device→host transfer on the caller's thread; the result is
    immutable w.r.t. further training steps."""
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    net.init()
    return {
        "kind": (
            "multilayer" if isinstance(net, MultiLayerNetwork) else "graph"
        ),
        "conf_json": net.conf.to_json(),
        "params": jax.tree.map(np.asarray, net.params),
        "updater_state": jax.tree.map(np.asarray, net.updater_state),
        "state": jax.tree.map(np.asarray, net.state),
        "iteration": net.iteration,
    }


def write_snapshot(snap: Dict[str, Any], path: str) -> None:
    """Write a snapshot dict to ``path`` as one zip, atomically."""
    buf = io.BytesIO()
    np.savez(buf, **_flatten(snap["params"]))
    extras = {
        "updater_state": snap["updater_state"],
        "state": snap["state"],
        "iteration": snap["iteration"],
    }
    tmp = path + ".tmp"
    with zipfile.ZipFile(tmp, "w", zipfile.ZIP_DEFLATED) as z:
        z.writestr("type", snap["kind"])
        z.writestr("conf.json", snap["conf_json"])
        z.writestr("params.npz", buf.getvalue())
        z.writestr("extras.pkl", pickle.dumps(extras))
    os.replace(tmp, path)  # atomic commit: no torn checkpoints on crash


def write_model(net, path: str) -> None:
    """Serialize a MultiLayerNetwork or ComputationGraph to one zip file."""
    write_snapshot(snapshot(net), path)


def restore_model(path: str, updater_state: bool = True):
    """Load a model zip back into the right network class.
    ``updater_state=False`` is for a net that will only be served: the
    optimizer's moments (Adam: twice the parameters) are neither put on
    the device nor kept from ``init()``, and ``net.updater_state``
    comes back empty, so ``fit`` on that net fails rather than training
    from zeroed moments."""
    with zipfile.ZipFile(path) as z:
        kind = z.read("type").decode()
        conf_json = z.read("conf.json").decode()
        npz = np.load(io.BytesIO(z.read("params.npz")))
        params = _unflatten({k: npz[k] for k in npz.files})
        extras = pickle.loads(z.read("extras.pkl"))

    if kind == "multilayer":
        from deeplearning4j_tpu.nn.conf.multi_layer import (
            MultiLayerConfiguration,
        )
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        net = MultiLayerNetwork(
            MultiLayerConfiguration.from_json(conf_json)
        ).init()
    else:
        from deeplearning4j_tpu.nn.conf.graph_conf import (
            ComputationGraphConfiguration,
        )
        from deeplearning4j_tpu.nn.graph import ComputationGraph

        net = ComputationGraph(
            ComputationGraphConfiguration.from_json(conf_json)
        ).init()

    net.params = _merge_into(net.params, params)
    net.updater_state = (
        jax.tree.map(jnp.asarray, extras["updater_state"])
        if updater_state else {})
    net.state = jax.tree.map(jnp.asarray, extras["state"])
    net.iteration = int(extras["iteration"])
    return net
