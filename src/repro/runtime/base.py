"""The stage-runtime layer: what a SWARM "peer" runs.

The elastic scheduler (``repro.core``) decides *where* a microbatch goes;
a :class:`StageExecutor` decides *how* the chosen peer executes its
stages.  Unifying the previously-disjoint stage implementations — the
eager per-peer ``StageProgram`` math and the compiled GSPMD path of
``repro.dist`` — behind this protocol is what lets a heterogeneous swarm
(paper §3, and Diskin et al.'s pooled-hardware setting) mix peers that
are a lone T4 with peers that are an 8-device mesh slice, inside one
pipeline:

* :class:`~repro.runtime.numeric.NumericExecutor` — single-device stage
  math behind a process-wide compile cache (one jit per stage shared by
  every peer of that stage, instead of per-peer re-tracing);
* :class:`~repro.runtime.mesh.MeshExecutor` — the stage step sharded
  over a device mesh via the ``repro.dist`` rules (data-parallel within
  the peer);
* :class:`~repro.runtime.pipeline.PipelineExecutor` — a contiguous
  *span* of stages ``[lo, hi)`` fused into one jitted step (the paper's
  square-cube rebalancing: well-provisioned peers hold more of the
  model), intra-span boundaries never crossing the host.

An executor's identity is its ``stages`` range — ``range(s, s+1)`` for
the single-stage backends.  Every state operation that the scheduler
performs per pipeline stage (gradient export, the optimizer-step install,
snapshot/restore) takes an explicit ``stage`` so a span peer is
per-stage addressable: it occupies one All-Reduce group per covered
stage, its checkpoint cuts are ordinary single-stage snapshots, and a
dying span peer hands per-stage state to single-stage peers (and vice
versa for merges).

Executors are *stateless* with respect to training progress: all mutable
state lives in the :class:`StageState` the scheduler hands in, so N
peers of one stage share one executor, and a peer migrating between
stages (or resizing its span) just swaps executors via ``for_span``.
``snapshot``/``restore`` speak host-side (numpy) trees — the common wire
format for peer-to-peer state downloads (numeric ↔ mesh ↔ pipeline in
any direction) and for ``repro.ckpt``, which is how a stage that lost
all its peers resumes from the latest completed step instead of step 0
(Varuna-style elastic restart).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Hashable, Iterable, Optional, Protocol, \
    runtime_checkable

import jax
import jax.numpy as jnp

from repro import obs

Tree = Any

# slot names with executor-protocol semantics of their own: "grads" is
# the per-stage gradient accumulator (accumulate / export_grads /
# zero_grads), "opt" the optimizer state (export_state / adopt_step).
# They travel in the snapshot's TOP-LEVEL fields ("opt"; grads never
# travel — a download or step never imports gradients), not under
# "slots", which keeps the single-stage snapshot format bit-compatible
# with every pre-slot checkpoint and hand-off.
GRADS_SLOT = "grads"
OPT_SLOT = "opt"
CORE_SLOTS = (GRADS_SLOT, OPT_SLOT)


class StageState:
    """Replicated executor-owned state for one pipeline stage — or, for
    a span backend, the per-stage-keyed bundle of them (``per_stage``).

    Owned by the executor protocol: schedulers treat it as an opaque
    handle and go through executor methods (``accumulate``, ``snapshot``,
    ``restore``, ``adopt_step``) for every mutation that touches device
    memory.  ``stage_view(s)`` is the read path the scheduler uses for
    per-stage bookkeeping (token counts for the All-Reduce weighting,
    the last stage's loss sum): it returns ``self`` on single-stage
    states and the stage-``s`` sub-state on span states, so span peers
    keep exact per-stage accounting (the ledger may admit one covered
    stage of a microbatch and skip another).

    Besides ``params``, everything an executor owns for a stage lives in
    named *keyed slots* — ``slots[name]`` is a ``{key: tree}`` dict.
    Training uses two of them: ``slots["grads"]["acc"]`` (the gradient
    accumulator) and ``slots["opt"]["state"]`` (optimizer state), still
    reachable through the ``grad_acc``/``opt`` properties every caller
    already uses.  Serving adds ``slots["kv"]`` keyed by session id (a
    decode cache per live session) — the same churn machinery
    (snapshot/restore, warm joins, per-stage hand-offs) moves any slot,
    which is what lets KV caches ride peer lifecycle events exactly like
    grads and opt do.
    """

    def __init__(self, params: Tree = None, opt: Tree = None,
                 grad_acc: Tree = None, loss_sum: float = 0.0,
                 token_count: int = 0, version: int = 0,
                 per_stage: Optional[dict[int, "StageState"]] = None):
        self.params = params
        self.slots: dict[str, dict[Hashable, Tree]] = {}
        if opt is not None:
            self.opt = opt
        if grad_acc is not None:
            self.grad_acc = grad_acc
        self.loss_sum = loss_sum
        self.token_count = token_count
        self.version = version
        # span backends: global stage id -> per-stage StageState; the
        # outer object then carries no tensors of its own
        self.per_stage = per_stage
        # a last stage's pending loss forward (``SavedForward``): the
        # residuals its backward consumes.  Not a slot: it never travels,
        # and every state install drops it (``reset_progress``, and the
        # zero-copy alias of ``repro.core.peer``)
        self.saved_fwd: Optional[SavedForward] = None

    # ------------------------------------------------------------- slots
    def slot(self, name: str) -> dict[Hashable, Tree]:
        """The named keyed slot, created empty on first touch."""
        return self.slots.setdefault(name, {})

    def drop_slot(self, name: str, key: Optional[Hashable] = None) -> None:
        """Forget one entry (``key``) or the whole slot (``key=None``)."""
        if key is None:
            self.slots.pop(name, None)
            return
        ent = self.slots.get(name)
        if ent is not None:
            ent.pop(key, None)
            if not ent:
                del self.slots[name]

    @property
    def opt(self) -> Tree:
        return self.slots.get(OPT_SLOT, {}).get("state")

    @opt.setter
    def opt(self, value: Tree) -> None:
        if value is None:
            self.slots.pop(OPT_SLOT, None)
        else:
            self.slot(OPT_SLOT)["state"] = value

    @property
    def grad_acc(self) -> Tree:
        return self.slots.get(GRADS_SLOT, {}).get("acc")

    @grad_acc.setter
    def grad_acc(self, value: Tree) -> None:
        if value is None:
            self.slots.pop(GRADS_SLOT, None)
        else:
            self.slot(GRADS_SLOT)["acc"] = value

    # ------------------------------------------------------------- views
    def stage_view(self, stage: Optional[int] = None) -> "StageState":
        if self.per_stage is None or stage is None:
            return self
        return self.per_stage[stage]

    def views(self) -> list["StageState"]:
        return (list(self.per_stage.values()) if self.per_stage is not None
                else [self])

    def zero_grads(self):
        if self.per_stage is not None:
            for st in self.per_stage.values():
                st.zero_grads()
        if self.grad_acc is not None:
            self.grad_acc = jax.tree.map(jnp.zeros_like, self.grad_acc)
        self.loss_sum = 0.0
        self.token_count = 0

    def reset_progress(self):
        """Fresh accumulator (zeros shaped/placed like ``params``),
        cleared loss/token counters and no pending saved forward — the
        tail of every state install (restore, adopt_step): a download or
        step never imports grads, and a forward of the old params is
        never a backward's.
        Non-core slots (e.g. serving KV) are untouched: adopting an
        optimizer step must not evict live sessions."""
        self.grad_acc = jax.tree.map(jnp.zeros_like, self.params)
        self.loss_sum = 0.0
        self.token_count = 0
        self.saved_fwd = None


@dataclasses.dataclass
class SavedForward:
    """One last-stage loss forward whose backward has not run yet: the
    call's own ``inp``, ``labels`` and ``params`` objects (a backward
    consumes ``saved`` only for these same objects), the forward's loss,
    and the pullback's residuals (``StageProgram.fwd_save``)."""
    inp: Tree
    labels: Any
    params: Tree
    loss: Any
    saved: list


@runtime_checkable
class StageExecutor(Protocol):
    """How a peer runs its pipeline stages (init / fwd / bwd / accumulate
    / snapshot / restore / wire-codec handling).

    ``run_fwd``/``run_bwd`` consume and produce *wire* tensors: whatever
    representation crosses between peers (the learned codecs' c-dim
    tensor, or the d-dim activation for ``none``/``int8``).  The int8
    round-trip that used to be special-cased in the trainer lives in
    ``wire_fwd``/``wire_bwd`` — the trainer is codec-agnostic.  Span
    backends apply the wire codec only at span *edges*; fused boundaries
    stay on-device inside ``run_fwd``/``run_bwd``.

    Per-stage state operations take ``stage=None`` meaning "the
    executor's sole stage" — single-stage backends accept only that (or
    their own stage id); span backends require an explicit covered
    stage for ``export_grads``/``export_state``/``adopt_step`` and for
    single-stage-formatted ``snapshot``/``restore``.
    """

    stage: int                     # entry stage (== stages.start)
    stages: range                  # contiguous span served, [lo, hi)
    n_stages: int
    compress_mode: str
    quant_block: int               # int8 wire codec block size
    device_count: int              # relative capacity of this backend
    fwd_flops_per_token: float     # whole-span totals
    bwd_flops_per_token: float

    # ---------------------------------------------------------- lifecycle
    def init_state(self, key: jax.Array) -> StageState: ...

    def for_span(self, span: range) -> "StageExecutor":
        """The sibling executor serving ``span`` on the same backend —
        how a peer migrates between stages, and how span peers split
        into single-stage peers and merge back (``for_stage`` is the
        width-1 shorthand)."""
        ...

    def for_stage(self, stage: int) -> "StageExecutor": ...

    def dp_shards(self, batch: int) -> int:
        """How many ways this backend actually splits a ``batch``-sized
        microbatch (the cost model's compute speedup).  1 whenever the
        placement would replicate instead of shard."""
        ...

    def session_program(self, total_len: int):
        """The serving :class:`repro.serve.programs.SessionProgram` for
        this executor's span at horizon ``total_len`` (prompt +
        generated tokens): fused prefill/decode whose KV caches live in
        the state's ``"kv"`` keyed slot.  Backends that cannot serve
        raise ``NotImplementedError``."""
        ...

    # ---------------------------------------------------------- execution
    def run_fwd(self, state: StageState, inp: Tree,
                labels: Optional[jax.Array] = None) -> Tree:
        """Span forward from the boundary input.  A span covering the
        last stage returns the token-sum loss; others return the
        outbound wire tensor.  A single-stage last stage whose programs
        offer ``fwd_save`` (``NumericExecutor``, decoder-only) also
        keeps the pullback's residuals on ``state.saved_fwd``, one
        forward's at most, for the ``run_bwd`` that follows."""
        ...

    def run_bwd(self, state: StageState, inp: Tree,
                dy: Optional[Tree] = None,
                labels: Optional[jax.Array] = None
                ) -> tuple[Optional[float], Optional[Tree], Tree]:
        """Span backward.  It recomputes the forward from ``inp`` (App.
        A), unless ``state.saved_fwd`` holds the residuals of a
        ``run_fwd`` on these same ``inp``, ``labels`` and params objects:
        then it consumes them and returns that forward's loss.
        Returns ``(loss, gx, gp)``; ``loss`` only when the span covers
        the last stage, ``gx`` None when it starts at 0.  Single-stage
        backends return ``gp`` as the stage's param tree; span backends
        return a dict keyed by *global stage id* so the scheduler can
        fold each covered stage independently (the ledger may admit a
        subset)."""
        ...

    # ------------------------------------------------- dispatch / collect
    def dispatch_fwd(self, state: StageState, inp: Tree,
                     labels: Optional[jax.Array] = None
                     ) -> Callable[[], Tree]:
        """Issue the span forward NOW and return a zero-arg *collect*
        thunk for its result.  The async tick's executor-side lever: on
        real hardware JAX dispatches the computation and returns before
        it finishes, so the peer can start microbatch ``k+1`` (or put
        ``k``'s boundary on the wire) while ``k`` still runs; calling
        the thunk blocks until the result is materialized.  Semantically
        ``collect()`` must equal ``run_fwd(state, inp, labels)`` —
        backends where dispatch is synchronous just close over the
        finished value."""
        ...

    def dispatch_bwd(self, state: StageState, inp: Tree,
                     dy: Optional[Tree] = None,
                     labels: Optional[jax.Array] = None
                     ) -> Callable[[], tuple[Optional[float],
                                             Optional[Tree], Tree]]:
        """Issue the span backward NOW; the returned thunk yields
        ``(loss, gx, gp)`` exactly as ``run_bwd`` would."""
        ...

    # --------------------------------------------------------- wire codec
    def wire_fwd(self, y: Tree) -> Tree:
        """Transform the forward output into what crosses the wire."""
        ...

    def wire_bwd(self, gx: Tree) -> Tree:
        """Transform the boundary cotangent into what crosses back."""
        ...

    # -------------------------------------------------------- accumulation
    def accumulate(self, state: StageState, gp: Optional[Tree],
                   loss: Optional[float], n_tokens: int,
                   stage: Optional[int] = None) -> None:
        """Fold one microbatch gradient into the (per-stage) accumulator."""
        ...

    def export_grads(self, state: StageState,
                     stage: Optional[int] = None) -> Tree:
        """Stage ``stage``'s accumulator in a form addable across that
        stage's peers on the scheduler's device (identity for
        single-device backends, host-gathered for mesh backends)."""
        ...

    def export_state(self, state: StageState,
                     stage: Optional[int] = None) -> tuple[Tree, Tree]:
        """``(params, opt)`` in scheduler-local form, for the optimizer
        step at the All-Reduce barrier."""
        ...

    def adopt_step(self, state: StageState, new_params: Tree,
                   new_opt: Tree, stage: Optional[int] = None) -> None:
        """Install post-optimizer-step state for one stage (placing it
        onto this backend's devices) and zero that stage's accumulator."""
        ...

    # ---------------------------------------------------- state transfer
    def snapshot(self, state: StageState, stage: Optional[int] = None,
                 slots: Iterable[str] = ()) -> Tree:
        """Host-side (numpy) ``{"params", "opt", "version"}`` tree — the
        wire format for peer-to-peer downloads and ``repro.ckpt``.  With
        an explicit ``stage``, span backends emit that covered stage in
        the SAME single-stage format, so span ↔ single hand-offs (and
        checkpoint cuts) are interchangeable.  ``slots`` names the extra
        keyed slots (e.g. ``"kv"``) to carry under a ``"slots"`` key;
        the default carries none, so training hand-offs and checkpoint
        cuts keep the historical format byte-for-byte and serving state
        never leaks into them."""
        ...

    def restore(self, state: StageState, snap: Tree,
                stage: Optional[int] = None,
                slots: Iterable[str] = ()) -> None:
        """Install a snapshot (device placement is the executor's job).
        A restore is a FULL state install: non-core slots not named in
        ``slots`` (or absent from the snapshot) are dropped — restoring
        a kv-carrying snapshot into a training-only peer sheds the kv
        slot, and restoring a training snapshot into a serving peer
        evicts its stale sessions."""
        ...

    # ------------------------------------------------------ keyed slots
    def export_slot(self, state: StageState, name: str, key: Hashable,
                    stage: Optional[int] = None) -> Tree:
        """One slot entry as a host (numpy) tree — the wire format for
        per-session hand-offs (e.g. prefill → decode KV transfer)."""
        ...

    def install_slot(self, state: StageState, name: str, key: Hashable,
                     value: Tree, stage: Optional[int] = None) -> None:
        """Place one slot entry onto this backend's devices."""
        ...

    def drop_slot(self, state: StageState, name: str,
                  key: Optional[Hashable] = None,
                  stage: Optional[int] = None) -> None:
        """Forget one slot entry (or, with ``key=None``, the slot)."""
        ...


def host_snapshot(state: StageState, slots: Iterable[str] = ()) -> Tree:
    """Default single-stage ``snapshot``: pull params/opt to host numpy,
    plus any requested non-core ``slots`` present on the state."""
    snap = {"params": jax.device_get(state.params),
            "opt": jax.device_get(state.opt),
            "version": state.version}
    extra = {name: {k: jax.device_get(v)
                    for k, v in state.slots[name].items()}
             for name in slots
             if name not in CORE_SLOTS and name in state.slots}
    if extra:
        snap["slots"] = extra
    return snap


def install_snapshot(state: StageState, snap: Tree,
                     slots: Iterable[str] = (),
                     place=None) -> None:
    """Default single-stage ``restore`` body: install params/opt/version
    (placed via ``place``, default ``jnp.asarray``), replace the state's
    non-core slots with the requested ones from the snapshot, and reset
    training progress.  Executors with their own placement (mesh) pass
    ``place``; the slot entries always place via ``jnp.asarray`` (KV
    trees are per-peer, never sharded)."""
    place = place or (lambda t: jax.tree.map(jnp.asarray, t))
    state.params = place(snap["params"])
    state.opt = (place(snap["opt"])
                 if snap.get("opt") is not None else None)
    state.version = int(snap.get("version", 0))
    for name in [n for n in state.slots if n not in CORE_SLOTS]:
        del state.slots[name]
    carried = snap.get("slots", {})
    for name in slots:
        if name in CORE_SLOTS or name not in carried:
            continue
        state.slot(name).update(
            {k: jax.tree.map(jnp.asarray, v)
             for k, v in carried[name].items()})
    state.reset_progress()


def slot_export(view: StageState, name: str, key: Hashable) -> Tree:
    """Default ``export_slot`` body over one stage view."""
    return jax.device_get(view.slot(name)[key])


def slot_install(view: StageState, name: str, key: Hashable,
                 value: Tree) -> None:
    """Default ``install_slot`` body over one stage view."""
    view.slot(name)[key] = jax.tree.map(jnp.asarray, value)


def exec_span(method: Callable) -> Callable:
    """Run an executor method inside the ``repro.exec.<method name>``
    span (:mod:`repro.obs`).  Its stat is ``stage``: the call's
    ``stage=`` keyword when given, else the executor's sole stage; a span
    executor's whole-span calls carry ``lo`` and ``hi`` instead."""
    name = "exec." + method.__name__

    @functools.wraps(method)
    def spanned(self, *args, **kwargs):
        stage = kwargs.get("stage")
        if stage is not None:
            where = {"stage": stage}
        elif len(self.stages) == 1:
            where = {"stage": self.stage}
        else:
            where = {"lo": self.stages.start, "hi": self.stages.stop}
        with obs.span(name, **where):
            return method(self, *args, **kwargs)
    return spanned


def fold_grads(acc: Tree, g: Tree) -> Tree:
    """``acc + g`` leaf by leaf, in the accumulator's dtype."""
    return jax.tree.map(lambda a, b: a + b.astype(a.dtype), acc, g)


# donated-accumulator fold shared by every backend: one jit object, jax
# caches the compiled fold per (tree structure, shapes, shardings).
# Donating arg 0 makes the add in-place — the old grad_acc buffer is
# dead the moment it returns (StageState owns it exclusively).
_accumulate = jax.jit(fold_grads, donate_argnums=(0,))


def fold_into(state: StageState, gp: Optional[Tree],
              loss: Optional[float], n_tokens: int, stage: int) -> None:
    """Default ``accumulate``: fold one microbatch gradient + bookkeeping
    into ``state``, pipeline stage ``stage``'s view (identical for
    single-device and mesh backends — the donated jit respects whatever
    placement the trees carry).  Runs in the ``repro.exec.accumulate``
    span."""
    with obs.span("exec.accumulate", stage=stage):
        if gp is not None:
            state.grad_acc = _accumulate(state.grad_acc, gp)
        state.token_count += n_tokens
        if loss is not None:
            state.loss_sum += loss


def single_stage(ex: StageExecutor, stage: Optional[int]) -> None:
    """Guard for single-stage backends' ``stage=`` keywords."""
    if stage is not None and stage != ex.stage:
        raise ValueError(
            f"{type(ex).__name__} serves stage {ex.stage}, not {stage}")


def _int8_roundtrip_tree(tree: Tree, quant_block: int,
                         use_kernel: bool = False) -> Tree:
    """int8-round-trip every floating leaf of a wire payload, passing
    integer leaves (e.g. the token ids riding a whisper boundary tree)
    through untouched.  Plain activations are the single-leaf case.
    ``use_kernel`` routes through the fused single-launch Pallas round
    trip (same codes)."""
    if use_kernel:
        return _kernel_roundtrip_tree(tree, quant_block)
    from repro.compression.quant8 import _roundtrip
    return _map_floating(lambda a: _roundtrip(a, quant_block), tree)


@functools.partial(jax.jit, static_argnums=(1,))
def _kernel_roundtrip_tree(tree: Tree, quant_block: int) -> Tree:
    """The Pallas round trip, jitted: one program per payload shape,
    where an eager call traced and lowered the kernel again each time."""
    from repro.kernels.boundary.ops import int8_roundtrip
    return _map_floating(
        lambda a: int8_roundtrip(a, quant_block, quant_block, True), tree)


def _map_floating(rt: Callable, tree: Tree) -> Tree:
    return jax.tree.map(
        lambda a: rt(a)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a,
        tree)


def _wire_use_kernel(ex: StageExecutor) -> bool:
    return getattr(getattr(ex, "cfg", None), "kernels", "jnp") == "pallas"


def wire_fwd_codec(ex: StageExecutor, y: Tree) -> Tree:
    """Shared ``wire_fwd`` codec step: int8 quantize-on-send on live
    span-edge boundaries.  Learned codecs already emitted the c-dim wire
    tensor inside the stage program; ``none`` crosses raw; a span whose
    last covered stage is the pipeline's last emits a loss, not a
    boundary — and fused (intra-span) boundaries never reach here.
    Runs in the ``repro.wire.fwd`` span; ``stage`` is the sending
    stage."""
    with obs.span("wire.fwd", stage=ex.stages.stop - 1):
        if ex.compress_mode == "int8" and ex.stages.stop < ex.n_stages:
            return _int8_roundtrip_tree(y, ex.quant_block,
                                        _wire_use_kernel(ex))
        return y


def wire_bwd_codec(ex: StageExecutor, gx: Optional[Tree]
                   ) -> Optional[Tree]:
    """Shared ``wire_bwd`` codec step: int8 quantizes the boundary
    cotangent (None when the span starts at stage 0 — nothing crosses
    back).  Runs in the ``repro.wire.bwd`` span; ``stage`` is the
    sending (entry) stage."""
    with obs.span("wire.bwd", stage=ex.stage):
        if gx is not None and ex.compress_mode == "int8":
            return _int8_roundtrip_tree(gx, ex.quant_block,
                                        _wire_use_kernel(ex))
        return gx
