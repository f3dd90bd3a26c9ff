"""Logical-axis -> mesh-axis sharding rules and NamedSharding builders.

Every parameter / cache tensor in the repo carries logical axis names on
its :class:`~repro.models.params.ParamSpec` (``("embed", "heads",
"head_dim")`` etc.).  :class:`ShardingRules` maps those names onto the
production mesh ``("pod", "data", "model")`` with two safety rules,
applied uniformly here and in :mod:`repro.dist.constrain`:

* **divisibility fallback** — a dim whose size does not divide the
  product of its mesh axes is dropped to replication (e.g. 4 kv-heads on
  a 16-way ``model`` axis);
* **first-use-wins** — a mesh axis may appear only once per
  PartitionSpec; later dims that want an already-taken axis replicate
  instead (e.g. a square ``("mlp", "embed2")`` weight).

``DEFAULT_RULES`` is FSDP-over-``data`` + tensor-parallel-over-``model``:
the paper's SWARM stages are *internally* data+tensor parallel, while the
``pod`` axis is reserved for the pipeline (``state_shardings(...,
pipeline=True)`` maps the stacked ``layers`` dim onto it).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax

from repro.dist.constrain import AxisSpec, resolve_spec
from repro.models import params as P

Tree = Any


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """One ``logical axis name -> mesh axes`` table (str | tuple | None)."""

    rules: dict[str, AxisSpec]

    def with_rules(self, **overrides: AxisSpec) -> "ShardingRules":
        return ShardingRules(rules={**self.rules, **overrides})

    def spec_for(self, names, shape, mesh) -> jax.sharding.PartitionSpec:
        """PartitionSpec for one tensor with logical ``names`` per dim."""
        return resolve_spec([self.rules.get(n) for n in names], shape, mesh)

    def sharding_for(self, spec: P.ParamSpec, mesh) -> jax.sharding.NamedSharding:
        return jax.sharding.NamedSharding(
            mesh, self.spec_for(spec.axes, spec.shape, mesh))


DEFAULT_RULES = ShardingRules(rules={
    # structural dims
    "layers": None,           # stacked-layer dim; -> "pod" under pipeline
    "stage": "pod",
    # weight dims
    "embed": "data",          # FSDP: shard the embed dim over data
    "embed2": "model",        # second embed dim of square projections
    "mlp": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "qk_dim": None,
    "v_dim": None,
    "vocab": "model",
    "experts": "model",       # expert parallelism shares the model axis
    "expert_mlp": None,
    "kv_lora": None,
    "q_lora": None,
    "bottleneck": "model",    # codec wire dim: TP like "mlp" (w_c/w_d are
                              # [embed, bottleneck] / [bottleneck, embed])
    "state": None,
    "conv": None,
    "pos": None,
    "null": None,
    # activation / cache dims
    "batch": ("pod", "data"),
    "kv_seq": None,
})


def _model_specs(cfg) -> Tree:
    from repro.train import steps as steps_lib   # lazy: steps imports models
    return steps_lib.model_specs(cfg)


def _spec_shardings(spec_tree: Tree, mesh,
                    rules: ShardingRules) -> Tree:
    return jax.tree.map(lambda s: rules.sharding_for(s, mesh),
                        spec_tree, is_leaf=P.is_spec)


def param_shardings(cfg, mesh, rules: Optional[ShardingRules] = None) -> Tree:
    """NamedSharding tree matching ``model_specs(cfg)`` / the params tree."""
    return _spec_shardings(_model_specs(cfg), mesh, rules or DEFAULT_RULES)


def stage_param_shardings(specs: Tree, mesh,
                          rules: Optional[ShardingRules] = None) -> Tree:
    """NamedSharding tree for an arbitrary ParamSpec tree — e.g. one
    pipeline stage's ``StageProgram.specs``, which is how
    :class:`repro.runtime.mesh.MeshExecutor` places a stage's parameters
    on its peer-local mesh by their logical axes."""
    return _spec_shardings(specs, mesh, rules or DEFAULT_RULES)


def state_shardings(cfg, mesh, *, pipeline: bool = False,
                    rules: Optional[ShardingRules] = None) -> Tree:
    """Shardings for the ``{"params", "opt", "step"}`` adamw train state.

    ``pipeline=True`` additionally maps the stacked ``layers`` dim onto
    ``pod`` so each pipeline stage owns exactly its slice of every
    layer-stacked weight (and of the matching optimizer moments).
    """
    rules = rules or DEFAULT_RULES
    if pipeline:
        rules = rules.with_rules(layers="pod", stage="pod")
    psh = param_shardings(cfg, mesh, rules)
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return {"params": psh,
            "opt": {"m": psh, "v": psh, "count": repl},
            "step": repl}


def batch_shardings(cfg, mesh, specs: Tree,
                    batch_axis: AxisSpec = ("pod", "data")) -> Tree:
    """Shardings for an input-batch tree: batch dim over ``batch_axis``.

    mrope ``positions`` are ``[3, B, S]`` — the batch dim is dim 1 there
    (mirrors ``steps._split_microbatches``); everything else is batch-major.
    """
    del cfg

    def one(path, s):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        axes: list[AxisSpec] = [batch_axis] + [None] * (s.ndim - 1)
        if name == "positions" and s.ndim >= 2:
            axes = [None, batch_axis] + [None] * (s.ndim - 2)
        return jax.sharding.NamedSharding(
            mesh, resolve_spec(axes, s.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, specs)


def cache_shardings_from_specs(cfg, mesh, specs: Tree,
                               batch_axis: AxisSpec = ("pod", "data"),
                               rules: Optional[ShardingRules] = None) -> Tree:
    """Shardings for decode-cache ParamSpec trees (logical axes intact).

    Caches follow the param rules (kv-heads over ``model`` etc.) except
    that their ``batch`` dim tracks the cell's batch axis — inference
    cells fold ``pod`` into data parallelism, so the caller decides.
    """
    del cfg
    rules = (rules or DEFAULT_RULES).with_rules(batch=batch_axis)
    return _spec_shardings(specs, mesh, rules)
