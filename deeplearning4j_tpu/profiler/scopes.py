"""The names the program gives its parts in the device trace.

A scope is ``jax.named_scope``: compile-time metadata on the operations
traced inside it (an HLO instruction's ``op_name``, the ``tf_op`` of its
events in a profile), no operation, no operand, nothing at run time. The
lowered text without locations is the same with and without them.

One vocabulary, here, so that a reader of a trace
(``benchmark/opscopes.py``) and the layers agree on it and a typo fails
where it is written. Names are KINDS, never layer indices: the copies of
a block in unrolled layers add up.

``GROUPS``: a group and, under it, the children a layer may name.
``PHASES``: what an engine program is for, the first scope of its body.
A path is ``[phase/]group[/child]``; a reader takes the FIRST group
after the phase, so a norm inside ``attn/qkv`` is attention's.

- ``embed``: the ids' gather / one-hot projection, its scale, a layer's
  preprocessor.
- ``norm``: LayerNorm, RMSNorm, gated RMSNorm, QK-norm.
- ``attn`` (``qkv``, ``rope``, ``core``, ``cache``, ``out``): the
  projections and the head split; rotary positions; the attention
  program (flash, paged kernel, gather, dense); the writes into the
  pool / the ring; ``Wo``, bias, gate, residual.
- ``ffn``: the dense feed-forward.
- ``moe`` (``route``, ``sort``, ``experts``, ``combine``, ``shared``):
  the router; the pairs' sort and gather; the grouped products; the
  scatter back, the gate-weighted sum and the cotangents' selects; the
  shared expert.
- ``mixer`` (``proj``, ``conv``, ``ssm``): the in/out projections and
  their split; the causal / short convolution; the chunked scan and the
  one-step update.
- ``eva`` (``qkv``, ``window``, ``summaries``, ``write``, ``out``): the
  EVA mixer (nn/layers/eva.py): its projections; the exact keys of the
  aligned window (the paged kernel's causal call, the gather, the
  tile); the summaries' walk and the merge of the two into one
  softmax; the chunk's keys into the pool and a completed chunk pooled
  to its summary entry; ``Wo`` and the residual.
- ``head`` (``logits``, ``loss``, ``sample``): the output layer; the
  score; ``sample_tokens``.
- ``cast``: masters to the compute dtype, activations' casts.
- ``update`` (``step``, ``health``): normalise, updater, subtract;
  ``grad_health``.
- ``tables``: what a paged layer derives from the block tables, the
  engine's ``seen`` / ``kept``.

**The one call no scope wraps.** The attention core's whole-sequence
program is the library's (``AttentionImpl._attend_core`` ->
``_flash_attention``): ``jit(_splash_attention)``, the block-sparse
kernel, under ``vmap`` over the batch (and over the KV heads where
they are grouped) and, under a gradient,
``jvp(vmap(jit(_splash_attention)))`` forward and
``transpose(jvp(vmap(jit(_splash_attention))))`` backward; its kernels
carry their own names (``splash_mha_fwd_residuals``,
``splash_mha_dkv_no_residuals``; ``splash_mqa_*`` for grouped heads). ``_attend_core`` leaves that call bare
and its callers call it from outside every scope, so the library's own
entry is the one name on its operations (the kernels, the backward's
``di`` product and the sum of the fused backward's partial dQ) and a
reader charges it by ``LIBRARY_SCOPES``. The entry before it,
``jit(flash_attention)``, HAD to stay bare: a Pallas call without a
name of its own is named after the innermost entry of its name stack,
wrappers and all, and a scope between the ``jvp`` and the call renamed
the forward kernel (``jvp_jit_flash_attention__`` became
``flash_attention``); it stays listed for traces recorded before the
kernel changed (the benchmark's own tests cut such paths). The kernels
jitted under their own names (``_paged_flash_attention``, ``gmm`` /
``tgmm``, ``_ssm_step_update``) keep them inside a scope.
"""

from __future__ import annotations

import contextlib

import jax

GROUPS = {
    "embed": (),
    "norm": (),
    "attn": ("qkv", "rope", "core", "cache", "out"),
    "ffn": (),
    "moe": ("route", "sort", "experts", "combine", "shared"),
    "mixer": ("proj", "conv", "ssm"),
    "eva": ("qkv", "window", "summaries", "write", "out"),
    "head": ("logits", "loss", "sample"),
    "cast": (),
    "update": ("step", "health"),
    "tables": (),
}
PHASES = ("admit", "decode")
#: name-stack entries of library code that stand for a path of the
#: vocabulary (the module docstring says why no scope is around them)
LIBRARY_SCOPES = {"jit(_splash_attention)": "attn/core",
                  "jit(flash_attention)": "attn/core"}


def scope(path: str):
    """A ``jax.named_scope`` an entry of ``path``, which is a phase, a
    group or ``group/child`` of the vocabulary; anything else raises,
    here and not on entry. (One scope an entry: a gradient wraps the
    first entry after it, ``transpose(jvp(attn))/qkv``, and a reader
    cuts a path at ``/``.)"""
    group, _, child = path.partition("/")
    if not (path in PHASES or group in GROUPS
            and (not child or child in GROUPS[group])):
        raise ValueError(
            f"scope {path!r} is not in the vocabulary: a phase "
            f"{PHASES}, a group or group/child of {GROUPS}")
    return _entered(path.split("/"))


@contextlib.contextmanager
def _entered(names):
    with contextlib.ExitStack() as stack:
        for name in names:
            stack.enter_context(jax.named_scope(name))
        yield
