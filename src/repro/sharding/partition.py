"""Logical-axis sharding rules -> NamedSharding (t5x-style).

Every model init returns an ``axes`` tree mirroring the params with
tuples of logical dim names; this module maps those names onto mesh axes
and builds the in/out shardings consumed by jit. Rules compose DP /
FSDP(ZeRO) / TP / EP / SP (see DESIGN.md §5):

  batch       -> ("pod", "data")   DP over pods x data
  embed       -> "data" iff fsdp   ZeRO parameter sharding
  qheads/mlp/vocab/experts/ssm_inner -> "model"   TP / EP
  kvheads     -> replicated        (KV heads < TP degree in all archs)
  seq         -> "data" iff sp     sequence parallelism for long prefill

KV-cache activations shard batch over ("pod","data") and heads over
"model" where divisible.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["PartitionRules", "make_rules", "spec_for_axes", "params_shardings",
           "batch_shardings", "cache_shardings", "logical_to_spec",
           "ROUTER_F32"]

# output axis of a router whose logits are computed in float32 (the
# held-experts layer's): replicated, and a weight that has it stays
# float32 in the train step's compute copy
ROUTER_F32 = "experts_f32"


@dataclasses.dataclass(frozen=True)
class PartitionRules:
    """logical dim name -> mesh axis (or None = replicate)."""

    table: Dict[str, Optional[object]]
    mesh: Mesh

    def spec(self, axes: Tuple[str, ...]) -> P:
        entries = []
        used = set()
        for name in axes:
            ax = self.table.get(name)
            # a mesh axis may appear only once per spec (e.g. experts and
            # mlp_e both map to "model": the first wins, rest replicate)
            if ax is None or ax in used or (isinstance(ax, tuple) and
                                            any(a in used for a in ax)):
                entries.append(None)
                continue
            if isinstance(ax, tuple):
                for a in ax:
                    used.add(a)
            else:
                used.add(ax)
            entries.append(ax)
        return P(*entries)


def make_rules(mesh: Mesh, *, fsdp: bool = True, sp: bool = False,
               kv_cache_heads_shardable: bool = False,
               shard_cache_seq: bool = False,
               shard_ssm_heads: bool = False,
               replicate_attn_heads: bool = False) -> PartitionRules:
    """Build the logical->mesh table.

    kv_cache_heads_shardable: KV-cache head dim divisible by TP degree
        (checked by the caller per-arch) -> shard cache heads on "model".
    shard_cache_seq: shard the KV-cache *sequence* dim over "data" —
        used for long-context decode where batch < DP degree.
    shard_ssm_heads: SSM state head dim divisible by TP degree.
    """
    has_pod = "pod" in mesh.axis_names
    dp = ("pod", "data") if has_pod else ("data",)
    table = {
        # --- weights -------------------------------------------------------
        "embed": "data" if fsdp else None,   # ZeRO/FSDP param shard
        # decode replicates attention heads: per-step attention weight
        # reads are tiny, and sharded heads force cache gathers (§Perf
        # iteration 6)
        "qheads": None if replicate_attn_heads else "model",
        "kvheads": None,                     # KV heads < TP in all archs
        "mlp": "model",
        "mlp_e": None,                       # expert FFN dim: EP already on "model"
        "vocab": "model",
        "experts": "model",                  # EP
        "experts_r": None,                   # router output dim (small)
        ROUTER_F32: None,                    # the same, kept f32 in compute
        "kv_lora": None,
        "layers": None,
        "ssm_inner": "model",                # mamba out_proj contraction dim
        "ssm_proj": None,                    # mixed z|x|B|C|dt projection dim
        "ssm_conv": None,
        "ssm_heads": "model" if shard_ssm_heads else None,
        "conv_width": None,
        "head_dim": None,
        "state": None,
        # --- activations / caches ------------------------------------------
        "batch": dp,
        "seq": "data" if sp else None,
        "seq_cache": "data" if shard_cache_seq else None,
        "kvheads_sep": "model" if kv_cache_heads_shardable else None,
        "shared_sites": None,
    }
    if shard_cache_seq:
        # long-context decode: batch (=1) cannot shard over DP — the
        # cache sequence dim carries the data axis instead
        table["batch"] = None
    return PartitionRules(table=table, mesh=mesh)


def logical_to_spec(rules: PartitionRules, axes_tree):
    is_axes = lambda t: (isinstance(t, tuple)
                         and all(isinstance(s, str) for s in t))
    return jax.tree.map(lambda t: rules.spec(t), axes_tree, is_leaf=is_axes)


def params_shardings(rules: PartitionRules, axes_tree):
    specs = logical_to_spec(rules, axes_tree)
    return jax.tree.map(lambda s: NamedSharding(rules.mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_shardings(rules: PartitionRules, batch_tree, *,
                    shard_seq: bool = False):
    """Shard every batch leaf's leading batch dim over DP (and optionally
    the second (sequence) dim over 'data' for SP prefill). The vlm
    ``positions`` leaf is (3, B, S): batch is dim 1."""
    mesh = rules.mesh
    dp = rules.table["batch"]

    def spec_for(leaf):
        nd = len(leaf.shape)
        if nd == 3 and leaf.shape[0] == 3:         # vlm positions (3,B,S)
            return P(None, dp)
        entries = [dp] + [None] * (nd - 1)
        return P(*entries)

    return jax.tree.map(lambda l: NamedSharding(mesh, spec_for(l)),
                        batch_tree)


def cache_shardings(rules: PartitionRules, cache_axes):
    return params_shardings(rules, cache_axes)
