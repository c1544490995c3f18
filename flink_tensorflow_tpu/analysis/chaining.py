"""Operator-chaining pass — fuse forward hops into single-thread chains.

Flink's production answer to per-hop record cost is operator chaining
(``StreamingJobGraphGenerator.isChainable``): forward-partitioned,
same-parallelism neighbors fuse into one task, and records pass between
them by direct method call — no queue, no serialization, no thread
wakeup.  This module is the plan-time half of that answer: it walks the
:class:`~flink_tensorflow_tpu.core.graph.DataflowGraph` and groups
transformations into chains; ``core/runtime.py`` executes one subtask
thread per chain, with a ``ChainedOutput`` invoking the next operator's
``process`` directly on the same thread.

An edge ``u -> d`` fuses only when ALL of these hold:

- the partitioner is a plain forward hop (keyed/broadcast/rebalance
  edges re-route records between subtasks and can never fuse);
- upstream and downstream parallelism are equal;
- ``d`` has exactly one input (two-input operators — connect/join/union
  merges — align multiple channels and must head their own task);
- ``u`` has exactly one outgoing edge (fan-out keeps chains linear);
- neither side opted out (``disable_chaining()``) and ``d`` was not
  pinned as a chain head (``start_new_chain()``);
- neither side is a gang operator (a gang owns the whole device mesh
  and blocks in collectives; fusing it would stall host work behind
  device sync) and their declared sharding axes agree — the same
  annotation the ``sharding-axis`` lint (analysis/rules.py) validates
  against the mesh;
- timer-driven operators (windows with wall-clock deadlines, async
  maps, process functions) never fuse INTO a LEGACY source chain: the
  ``SourceFunction.run()`` loop blocks inside the user function's
  sleep/IO and cannot serve wall-clock timers promptly.  Behind a
  worker head they fuse fine — the worker loop waits event-driven until
  the chain's earliest deadline.  SPLIT-source heads
  (``sources.SplitSourceOperator``, marked ``wakeable``) are exempt:
  their mailbox loop bounds every wait by the chain's earliest
  deadline, so timer-driven members fuse behind them too.
"""

from __future__ import annotations

import dataclasses
import typing

from flink_tensorflow_tpu.core.graph import DataflowGraph, Edge, Transformation
from flink_tensorflow_tpu.core.operators import Operator
from flink_tensorflow_tpu.core.partitioning import ForwardPartitioner

#: parallel.mesh.DATA_AXIS, unimported: the chaining pass runs inside
#: LocalExecutor._build and must not drag the (jax-importing) parallel
#: package onto the plan-construction path.
DATA_AXIS = "data"

#: Why a legacy-source chain is cut before a timer-driven member —
#: shared with the ``legacy-source-timer-chain`` lint (analysis/rules)
#: so the lint flags exactly the edges this pass refuses to fuse.
TIMER_CUT_REASON = (
    "timer-driven operator cannot chain into a source "
    "loop (wall-clock deadlines would wait on the "
    "source's own sleeps)"
)


def sharding_axes_of(function: typing.Any) -> typing.Optional[typing.Tuple[str, ...]]:
    """Mesh axes a function's jitted step shards its batch over, or None
    for host-side (unsharded) functions.

    Convention shared by the chaining pass and the ``sharding-axis``
    lint: functions declare ``sharding_axes = ("data", ...)``; gang
    functions (``is_gang``) that declare nothing default to ``("data",)``
    — the canonical batch placement of ``parallel.mesh.batch_sharding``.
    """
    if function is None:
        return None
    axes = getattr(function, "sharding_axes", None)
    if axes is not None:
        return tuple(axes)
    if getattr(function, "is_gang", False):
        return (DATA_AXIS,)
    return None


def sharding_fusion_conflict(
    up_op: typing.Optional[Operator], down_op: typing.Optional[Operator]
) -> typing.Optional[str]:
    """Why two adjacent operators must not share a thread on sharding
    grounds, or None when they are compatible.  Shared by
    :func:`compute_chains` and the lint registry so the two can never
    disagree."""
    up_fn = getattr(up_op, "function", None)
    down_fn = getattr(down_op, "function", None)
    if getattr(up_fn, "is_gang", False) or getattr(down_fn, "is_gang", False):
        return "gang operator owns the device mesh and never chains"
    up_axes = sharding_axes_of(up_fn)
    down_axes = sharding_axes_of(down_fn)
    if up_axes != down_axes and (up_axes is not None or down_axes is not None):
        return (
            f"mismatched sharding axes ({up_axes} vs {down_axes}) — the two "
            "steps place batches on different mesh axes"
        )
    return None


@dataclasses.dataclass
class ChainPlan:
    """The chaining decision for one graph.

    ``chains`` lists every chain in topological order, each a list of
    member transformations (head first).  Unchained operators appear as
    singleton chains, so the lists partition the graph exactly.
    """

    chains: typing.List[typing.List[Transformation]]
    #: member transformation id -> its chain's head transformation.
    head_of: typing.Dict[int, Transformation]
    #: why each non-fused candidate edge stayed a channel:
    #: (upstream id, downstream id) -> reason.  Forward edges only —
    #: keyed/broadcast edges are structurally unchainable and not listed.
    unchained_reasons: typing.Dict[typing.Tuple[int, int], str]

    def chain_of(self, t: Transformation) -> typing.List[Transformation]:
        head = self.head_of[t.id]
        for chain in self.chains:
            if chain[0].id == head.id:
                return chain
        raise KeyError(t.name)

    @property
    def chained_edge_count(self) -> int:
        return sum(len(c) - 1 for c in self.chains)

    def names(self) -> typing.List[typing.List[str]]:
        return [[t.name for t in chain] for chain in self.chains]

    def format_topology(self) -> str:
        """Human-readable chain topology for the analysis/inspector CLIs."""
        lines = []
        for chain in self.chains:
            members = " -> ".join(t.name for t in chain)
            tag = f"x{chain[0].parallelism}"
            fused = f", {len(chain) - 1} fused edge(s)" if len(chain) > 1 else ""
            lines.append(f"chain [{tag}{fused}]: {members}")
        return "\n".join(lines)


def _instantiate_quietly(
    graph: DataflowGraph,
) -> typing.Dict[int, typing.Optional[Operator]]:
    ops: typing.Dict[int, typing.Optional[Operator]] = {}
    for t in graph.transformations:
        try:
            ops[t.id] = t.operator_factory()
        except Exception:  # noqa: BLE001 - a broken factory is unchainable
            ops[t.id] = None
    return ops


def chainable_edge(
    edge: Edge,
    downstream: Transformation,
    *,
    out_degree: int,
    up_op: typing.Optional[Operator],
    down_op: typing.Optional[Operator],
) -> typing.Optional[str]:
    """Why ``edge`` must stay a channel, or None when it can fuse.

    ``out_degree`` is the upstream transformation's total outgoing edge
    count; ``up_op``/``down_op`` are plan-time operator instances (never
    opened) used for the gang/sharding/timer checks — pass None for a
    factory that failed, which conservatively blocks fusion.
    """
    u = edge.upstream
    if not isinstance(edge.partitioner, ForwardPartitioner):
        return f"{type(edge.partitioner).__name__} edge re-routes records"
    if u.parallelism != downstream.parallelism:
        return (
            f"parallelism changes ({u.parallelism} -> "
            f"{downstream.parallelism})"
        )
    if len(downstream.inputs) != 1:
        return "multi-input operator aligns several channels"
    if out_degree != 1:
        return "upstream fans out to several edges"
    if not u.chainable:
        return f"{u.name} has chaining disabled"
    if not downstream.chainable:
        return f"{downstream.name} has chaining disabled"
    if downstream.chain_start:
        return f"{downstream.name} starts a new chain"
    if up_op is None or down_op is None:
        return "operator factory failed at plan time"
    conflict = sharding_fusion_conflict(up_op, down_op)
    if conflict is not None:
        return conflict
    return None


def compute_chains(
    graph: DataflowGraph,
    *,
    operators: typing.Optional[typing.Dict[int, typing.Optional[Operator]]] = None,
    enabled: bool = True,
) -> ChainPlan:
    """Group the graph's transformations into execution chains.

    ``operators`` reuses the analyzer's plan-time instances; omitted,
    the factories run here (cheap by contract — ``open()`` never runs).
    ``enabled=False`` returns the degenerate plan (every operator its
    own chain) so a ``chaining=off`` comparison run shares this code
    path.  The decision is a pure function of the graph, so every
    process of a distributed cohort computes the identical plan.
    """
    order = graph.topological_order()
    if operators is None:
        operators = _instantiate_quietly(graph) if enabled else {}
    out_degree: typing.Dict[int, int] = {t.id: 0 for t in order}
    for t in order:
        for e in t.inputs:
            out_degree[e.upstream.id] += 1

    next_of: typing.Dict[int, Transformation] = {}
    reasons: typing.Dict[typing.Tuple[int, int], str] = {}
    if enabled:
        for t in order:
            for e in t.inputs:
                reason = chainable_edge(
                    e, t,
                    out_degree=out_degree[e.upstream.id],
                    up_op=operators.get(e.upstream.id),
                    down_op=operators.get(t.id),
                )
                if reason is None:
                    next_of[e.upstream.id] = t
                elif isinstance(e.partitioner, ForwardPartitioner):
                    reasons[(e.upstream.id, t.id)] = reason

    # LEGACY source chains cannot serve wall-clock timers (the source
    # loop blocks inside the user function's sleeps), so a source-headed
    # chain is CUT before its first timer-driven member — transitively,
    # not just at the source's own edge: source -> map -> window(timeout)
    # must split at map|window, leaving the window a worker head whose
    # loop waits event-driven until the chain's earliest deadline.
    # SPLIT sources (sources/, FLIP-27 model) are exempt: their loop
    # owns all waiting on a wakeable mailbox bounded by the chain's
    # earliest deadline, so timer-driven members fuse fine behind them.
    for t in order:
        if not t.is_source:
            continue
        head_op = operators.get(t.id)
        if head_op is not None and getattr(head_op, "wakeable", False):
            continue
        prev, cur = t, next_of.get(t.id)
        while cur is not None:
            op = operators.get(cur.id)
            if op is not None and op.uses_timers:
                del next_of[prev.id]
                reasons[(prev.id, cur.id)] = TIMER_CUT_REASON
                break
            prev, cur = cur, next_of.get(cur.id)

    chained_into = {d.id for d in next_of.values()}
    chains: typing.List[typing.List[Transformation]] = []
    head_of: typing.Dict[int, Transformation] = {}
    for t in order:
        if t.id in chained_into:
            continue
        chain = [t]
        cur = t
        while cur.id in next_of:
            cur = next_of[cur.id]
            chain.append(cur)
        chains.append(chain)
        for member in chain:
            head_of[member.id] = t
    return ChainPlan(chains=chains, head_of=head_of, unchained_reasons=reasons)
