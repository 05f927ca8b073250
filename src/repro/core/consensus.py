"""Consensus mixing operators: ``w = Pi x`` over the agent population.

Three execution paths, one semantics (paper eq. 5 / eq. 6):

1. **Stacked** (`mix_stacked`, `mix_pytree_stacked`) — every leaf carries a
   leading agent axis ``(N, ...)``; mixing is a dense matmul with ``Pi``.
   Used for CPU-scale simulation (tests, paper-figure benchmarks) and as
   the oracle the sharded paths are verified against.

2. **Sharded circulant** (`make_sharded_mix_fn`) — inside ``shard_map`` over
   a named agent mesh axis, a circulant ``Pi`` decomposes into static shift
   offsets, each lowering to one ``lax.ppermute`` (TPU: `collective-permute`
   over ICI neighbours).  This is the fixed-topology, neighbor-only
   communication pattern that is the paper's whole point: cost is
   ``degree * |params|`` point-to-point transfers instead of a global
   all-reduce.

3. **Sharded general** — non-circulant ``Pi`` falls back to
   ``all_gather`` + per-agent row contraction (cost ``N * |params|``; only
   sensible for small agent counts or dense graphs, where it matches the
   all-reduce cost anyway).

`FactoredMix` composes per-axis topologies as a Kronecker product
``Pi = Pi_pod (x) Pi_data`` — mixing sequentially over each mesh axis.  This
is our TPU-native extension for multi-pod meshes: a ring over the ``pod``
axis (scarce DCN links) crossed with a denser graph over the in-pod ``data``
axis (cheap ICI links).

Mixing strategies (the MixingProgram layer)
-------------------------------------------
How the wire stages compose per optimizer step is a first-class
**strategy** object (:class:`StaticMixing`, :class:`TimeVaryingMixing`,
:class:`MultiRoundMixing`), configured by a :class:`MixingProgram` and
carried inside :class:`FlatComm`.  Every strategy implements the same
contract — ``quantize_stage`` / ``exchange_stage`` / ``gather`` plus the
engine-facing ``continue_from_wire`` and the error-feedback
``quantize_ef`` — so both execution modes, both exchange schedules
(``sync`` / ``overlap``), the fused kernels, the wire-byte accounting, and
the dryrun dependency proof apply to any of them unchanged (see
ARCHITECTURE.md §mixing strategies).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import flatbuf
from repro.core.faults import (FaultSchedule, arrival_masked_pi,
                               trivial_faults)
from repro.core.faults import MAX_FAULT_PERIOD as _MAX_FAULT_PERIOD
from repro.core.topology import Topology, TopologySchedule, fixed_schedule
from repro.utils.tree import tree_weighted_sum

PyTree = Any
MixFn = Callable[[PyTree], PyTree]


# --------------------------------------------------------------------------
# MixingProgram: the configuration of the mixing-strategy layer
# --------------------------------------------------------------------------

MIXING_STRATEGIES = ("static", "time_varying", "multi_round")
MOMENTUM_MIXINGS = ("none", "mixed")

# the compressor axis: dense SR quantizers (aliases for ``exchange=``) and
# the biased EF-rail compressors (see repro.kernels.consensus_update.topk)
COMPRESSOR_KINDS = ("none", "int8", "fp8", "topk", "rank")


def parse_compressor(spec: str):
    """``"none" | "int8" | "fp8" | "topk:p" | "rank:r"`` -> ``(kind, param)``.

    ``param`` is the float density ``p in (0, 1]`` for ``topk``, the int
    rank ``r >= 1`` for ``rank``, and ``None`` for the dense kinds.
    ``"topk:auto:B"`` selects adaptive per-bucket density against a total
    byte budget ``B`` per neighbor (``param = ("auto", B)``; see
    :func:`repro.kernels.consensus_update.topk.topk_auto_k_rows`).
    Raises an actionable ``ValueError`` on malformed specs — this is the
    single parser behind ``--compressor`` and ``make_mixing_program``.
    """
    if not isinstance(spec, str):
        raise TypeError(f"compressor spec must be a str, got "
                        f"{type(spec).__name__}")
    kind, _, arg = spec.partition(":")
    if kind not in COMPRESSOR_KINDS:
        raise ValueError(
            f"unknown compressor {spec!r}; expected one of "
            f"{COMPRESSOR_KINDS[:3]} or 'topk:p' (0 < p <= 1) or "
            "'rank:r' (int r >= 1)")
    if kind in ("none", "int8", "fp8"):
        if arg:
            raise ValueError(f"compressor {kind!r} takes no parameter "
                             f"(got {spec!r})")
        return kind, None
    if not arg:
        raise ValueError(
            f"compressor {kind!r} needs a parameter: "
            + ("'topk:p' with density 0 < p <= 1 (e.g. 'topk:0.01')"
               if kind == "topk" else
               "'rank:r' with int rank r >= 1 (e.g. 'rank:4')"))
    if kind == "topk":
        if arg.startswith("auto:") or arg == "auto":
            _, _, barg = arg.partition(":")
            try:
                budget = int(barg)
            except ValueError:
                raise ValueError(
                    f"topk:auto needs an int byte budget per neighbor, got "
                    f"{barg!r} in {spec!r} (e.g. 'topk:auto:65536')") from None
            if budget < 1:
                raise ValueError(f"topk:auto byte budget must be >= 1, got "
                                 f"{budget} in {spec!r}")
            return kind, ("auto", budget)
        try:
            p = float(arg)
        except ValueError:
            raise ValueError(f"top-k density must be a float, got {arg!r} "
                             f"in {spec!r}; for adaptive per-bucket density "
                             f"use 'topk:auto:B' with a byte budget") from None
        if not (0.0 < p <= 1.0):
            raise ValueError(f"top-k density must be in (0, 1], got {p!r} "
                             f"in {spec!r}")
        return kind, p
    try:
        r = int(arg)
    except ValueError:
        raise ValueError(f"rank must be an int, got {arg!r} in {spec!r}") \
            from None
    if r < 1:
        raise ValueError(f"rank must be >= 1, got {r} in {spec!r}")
    return kind, r


@dataclasses.dataclass(frozen=True)
class MixingProgram:
    """What the consensus exchange does each optimizer step.

    * ``strategy="static"``      — one fixed ``Pi``, one round (the paper's
      setting; bit-for-bit today's path);
    * ``strategy="time_varying"``— ``Pi_t = schedule[t % period]`` selected
      by the optimizer step (B-connected sequences, gossip pairs);
    * ``strategy="multi_round"`` — ``rounds`` inner consensus rounds per
      gradient step, re-quantizing between rounds: ``x' = Pi^k x - a g``
      (i-CDSGD, Jiang et al. 1805.12120).  ``rounds`` also composes with
      ``time_varying`` (``Pi_t`` applied ``k`` times).

    ``error_feedback`` compresses ``residual + payload`` instead of the raw
    payload and carries the compression error in ``OptState.residual`` —
    the principled fix for quantization-noise accumulation (requires a
    quantized ``exchange``; the residual never crosses the wire).

    ``momentum_mixing="mixed"`` widens the wire to TWO payload trees: the
    momentum buffer rides alongside the params and is mixed with the same
    agent-interaction matrix — ``v' = mu (Pi v) - a g`` instead of
    ``v' = mu v - a g`` (Gao & Huang, 2010.11166).  With an unmixed
    momentum the disagreement dynamics of the joint ``(x, v)`` system
    contract at ``max(|lambda_2|, mu)`` through a non-normal coupling, so
    any per-step wire noise persists for ``~1/(1-mu)`` steps — the PR 2
    large-lr momentum/quantization instability; mixing ``v`` over the wire
    makes both dynamics contract together at ``|lambda_2|`` (see
    :func:`repro.core.lyapunov.momentum_consensus_contraction`).  Doubles
    the wire bytes at equal precision; momentum-capable fused optimizers
    only (CDMSGD family / CDAdam's first moment).

    ``staleness=S`` / ``faults=`` engage the **bounded-staleness ring**
    (``schedule="overlap"`` only): the overlap double-buffer generalizes to
    a depth-``S`` ring of each agent's own last-``S`` quantized wire
    generations (:class:`WireRing`); under the injected
    :class:`~repro.core.faults.FaultSchedule` each sender contributes the
    freshest generation that *arrived* (up to ``S`` steps stale) and the
    mixing weights renormalize over arrived neighbors — a dropped or
    over-stale neighbor's mass folds into the receiver's self term,
    preserving row-stochasticity.  The self term stays fresh and
    full-precision exactly as today: staleness and masking ride entirely in
    *which* carried buffers and *which* weights feed the existing
    self-separated fused update (no new kernel variants), and the per-step
    wire bytes are independent of ``S`` — a stale slot moves nothing.

    Built via :func:`make_mixing_program`, which validates everything at
    config time — never inside a traced step.
    """

    schedule: TopologySchedule
    strategy: str = "static"
    rounds: int = 1
    error_feedback: bool = False
    exchange: str = "f32"
    momentum_mixing: str = "none"
    # bounded-staleness fault tolerance: ring depth S and the injected
    # fault schedule (see repro.core.faults).  staleness=1 with no faults
    # is today's overlap double-buffer, bit-for-bit.
    staleness: int = 1
    faults: Optional[FaultSchedule] = None
    # the compressor axis: "none" | "int8" | "fp8" (dense aliases — they
    # normalize ``exchange`` and change nothing else, bit-for-bit) |
    # "topk:p" | "rank:r" (biased EF-rail compressors; require
    # error_feedback=True, validated in make_mixing_program)
    compressor: str = "none"
    # sparse operand form of the fused update: with the top-k wire the
    # *_update_sparse_2d kernels consume the TopKWire fields directly
    # (scatter-accumulate, O(k_rows) neighbor reads) instead of
    # densifying via _decompress_entry first (O(rows)).  Default on for
    # topk (resolved in make_mixing_program); False keeps the dense
    # decompress path as the reference oracle.
    sparse_update: bool = False

    @property
    def fault_tolerant(self) -> bool:
        """True iff the depth-S staleness ring / arrival-masked weight path
        is engaged (``staleness > 1`` or an injected fault schedule)."""
        return self.staleness > 1 or self.faults is not None

    @property
    def compressor_kind(self) -> str:
        return parse_compressor(self.compressor)[0]

    @property
    def compressor_param(self):
        """Density ``p`` (topk) / rank ``r`` (rank); None for dense kinds."""
        return parse_compressor(self.compressor)[1]

    @property
    def compressed(self) -> bool:
        """True iff a biased (top-k / rank-r) compressor rides the wire —
        the dense int8/fp8 aliases resolve to the existing exchange path."""
        return self.compressor_kind in ("topk", "rank")

    @property
    def is_trivial(self) -> bool:
        """True iff this is exactly the legacy single-round fixed-``Pi``
        program (whose sync path must stay bit-for-bit unchanged)."""
        return (self.strategy == "static" and self.rounds == 1
                and not self.error_feedback
                and self.momentum_mixing == "none"
                and not self.fault_tolerant
                and not self.compressed)

    @property
    def n_payloads(self) -> int:
        """Payload trees on the wire: params, plus the mixed momentum."""
        return 2 if self.momentum_mixing == "mixed" else 1

    def describe(self) -> dict:
        return {
            "strategy": self.strategy,
            "schedule": self.schedule.name,
            "period": self.schedule.period,
            "rounds": self.rounds,
            "error_feedback": self.error_feedback,
            "exchange": self.exchange,
            "momentum_mixing": self.momentum_mixing,
            "staleness": self.staleness,
            "faults": self.faults.describe() if self.faults else None,
            "compressor": self.compressor,
            "sparse_update": self.sparse_update,
        }


def make_mixing_program(
    topology_or_schedule,
    *,
    strategy: str = "static",
    rounds: int = 1,
    error_feedback: bool = False,
    exchange: str = "f32",
    momentum_mixing: str = "none",
    staleness: int = 1,
    faults: Optional[FaultSchedule] = None,
    compressor: str = "none",
    sparse_update: Optional[bool] = None,
) -> MixingProgram:
    """Validate + build a :class:`MixingProgram` at config time.

    Accepts a :class:`Topology` (wrapped in a period-1 schedule) or a
    :class:`TopologySchedule`.  ``strategy="static"`` with ``rounds > 1``
    is promoted to ``"multi_round"`` (they are the same family; ``k = 1``
    multi-round is literally the static strategy object).

    ``compressor="int8"|"fp8"`` are dense aliases: they normalize
    ``exchange`` to the same precision and change nothing else (bit-for-bit
    the existing quantized path).  ``"topk:p"`` / ``"rank:r"`` engage the
    biased EF-rail compressors, which REQUIRE ``error_feedback=True`` and
    exclude staleness/faults, inner rounds, and momentum mixing — each
    rejection below names the conflicting flags and the supported
    alternative.

    ``sparse_update=None`` resolves to True exactly for the top-k
    compressor (the sparse operand form of the fused update, see
    :class:`MixingProgram`); pass ``False`` to force the dense
    decompress-then-update reference path.  Explicit ``True`` with any
    other compressor is rejected — only the top-k wire has the compact
    scatter operand form.
    """
    _check_exchange(exchange)
    ckind, _cparam = parse_compressor(compressor)
    if sparse_update is None:
        sparse_update = ckind == "topk"
    elif sparse_update and ckind != "topk":
        raise ValueError(
            f"sparse_update=True needs --compressor topk:p / topk:auto:B "
            f"(got {compressor!r}): only the top-k wire has the compact "
            "gather-dequant-accumulate operand form — drop sparse_update "
            "or switch to a top-k compressor")
    if ckind in ("int8", "fp8"):
        if exchange not in ("f32", ckind):
            raise ValueError(
                f"--compressor {ckind} conflicts with --exchange "
                f"{exchange}: the dense compressor aliases ARE the "
                f"quantized exchange — drop --exchange or set it to "
                f"{ckind!r}")
        exchange = ckind
    if ckind in ("topk", "rank"):
        if not error_feedback:
            raise ValueError(
                f"--compressor {compressor} is a biased compressor and "
                "needs --error-feedback: without the EF residual "
                "(OptState.residual) the dropped mass accumulates and the "
                "consensus diverges (Karimireddy et al. 2019) — add "
                "--error-feedback, or use --compressor int8/fp8 for an "
                "unbiased dense wire")
        if staleness > 1 or faults is not None:
            raise ValueError(
                f"--compressor {compressor} is incompatible with "
                "--staleness > 1 / --fault-schedule: the EF residual "
                "telescoping it requires assumes every carried payload is "
                "consumed exactly one step later — use --compressor "
                "int8/fp8 (no EF) with the staleness ring instead")
        if rounds > 1 or strategy == "multi_round":
            raise ValueError(
                f"--compressor {compressor} is incompatible with "
                "--consensus-rounds > 1: inner i-CDSGD rounds re-compress "
                "partially mixed buffers without an EF residual to absorb "
                "the bias — use a single round, or --compressor int8/fp8 "
                "for multi-round")
        if momentum_mixing != "none":
            raise ValueError(
                f"--compressor {compressor} is incompatible with "
                "--momentum-mixing mixed: only the params payload rides "
                "the sparse/low-rank wire — use --compressor int8/fp8 to "
                "mix the momentum buffer, or momentum_mixing='none'")
        if ckind == "topk":
            if exchange not in ("f32", "int8"):
                raise ValueError(
                    f"--compressor {compressor} ships int8 SR-quantized "
                    f"compact values; --exchange {exchange} conflicts — "
                    "drop --exchange (the compact-value precision is part "
                    "of the top-k wire contract)")
            exchange = "int8"
        else:
            if exchange != "f32":
                raise ValueError(
                    f"--compressor {compressor} ships two dense f32 "
                    f"factors; --exchange {exchange} conflicts — drop "
                    "--exchange (quantizing the factors is not part of "
                    "the rank-r wire contract)")
    if isinstance(topology_or_schedule, Topology):
        schedule = fixed_schedule(topology_or_schedule)
    elif isinstance(topology_or_schedule, TopologySchedule):
        schedule = topology_or_schedule
    else:
        raise TypeError(f"expected Topology or TopologySchedule, got "
                        f"{type(topology_or_schedule).__name__}")
    if not isinstance(rounds, int) or rounds < 1:
        raise ValueError(f"consensus rounds must be an int >= 1, got {rounds!r}")
    if strategy not in MIXING_STRATEGIES:
        raise ValueError(f"unknown mixing strategy {strategy!r}; expected one "
                         f"of {MIXING_STRATEGIES}")
    if strategy == "static" and rounds > 1:
        strategy = "multi_round"
    if strategy == "multi_round" and rounds == 1:
        # k = 1 multi-round IS the static strategy — normalizing here makes
        # the equivalence bit-for-bit by construction (same legacy gather)
        strategy = "static"
    if strategy in ("static", "multi_round") and schedule.period != 1:
        raise ValueError(
            f"strategy={strategy!r} takes a fixed topology but the schedule "
            f"{schedule.name!r} has period {schedule.period}; use "
            "strategy='time_varying'")
    if error_feedback and exchange not in ("int8", "fp8") \
            and ckind not in ("topk", "rank"):
        raise ValueError(
            "--error-feedback needs a lossy wire to feed back: set "
            "--exchange int8/fp8 (quantization error) or --compressor "
            f"topk:p/rank:r (compression error); exchange={exchange!r} "
            "with a dense compressor has no error to carry")
    if momentum_mixing not in MOMENTUM_MIXINGS:
        raise ValueError(f"unknown momentum_mixing {momentum_mixing!r}; "
                         f"expected one of {MOMENTUM_MIXINGS}")
    if not isinstance(staleness, int) or staleness < 1:
        raise ValueError(f"staleness must be an int >= 1, got {staleness!r}")
    if faults is not None:
        if not isinstance(faults, FaultSchedule):
            raise TypeError(f"faults must be a FaultSchedule, got "
                            f"{type(faults).__name__}")
        if faults.n_agents != schedule.n_agents:
            raise ValueError(f"fault schedule covers {faults.n_agents} agents "
                             f"but the topology has {schedule.n_agents}")
        faults.validate()
        if faults.is_trivial:
            faults = None  # the all-arrive schedule IS the no-fault program
    if error_feedback and (staleness > 1 or faults is not None):
        raise ValueError(
            "--error-feedback is incompatible with --staleness > 1 / "
            "--fault-schedule: the residual telescoping assumes every "
            "carried wire payload is consumed exactly one step later, which "
            "bounded staleness breaks by design — drop --error-feedback "
            "(plain SR quantization is unbiased) or run staleness=1 with "
            "no fault schedule")
    return MixingProgram(schedule=schedule, strategy=strategy, rounds=rounds,
                         error_feedback=error_feedback, exchange=exchange,
                         momentum_mixing=momentum_mixing,
                         staleness=staleness, faults=faults,
                         compressor=compressor, sparse_update=sparse_update)


# --------------------------------------------------------------------------
# Flat-buffer fused-consensus support (see repro.core.flatbuf)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FlatComm:
    """Whole-model fused-update support carried inside :class:`CommOps`.

    ``gather(bufs, seed)`` maps the packed self-buffers to kernel-ready
    neighbor operands ``(neighbor_stacks, weights, scales, selfs)``: in the
    **stacked** mode it returns the full agent stack per bucket with the
    dense ``Pi`` as ``(A, A)`` weights (the fused kernels vmap over agent
    rows); in the **sharded** mode it issues one ``lax.ppermute`` per
    circulant shift offset per bucket and returns the ``(S, rows, 128)``
    stencil stack with ``(S,)`` weights.

    ``exchange`` selects the wire precision of the neighbor stacks:
    ``"f32"`` (native bucket dtype), ``"bf16"`` (cast), or ``"int8"`` /
    ``"fp8"`` (stochastic-rounding quantization with one f32 scale per
    128-lane row).  For quantized exchanges the per-bucket ``scales`` entry
    is the ``(..., rows, 1)`` stack the fused kernels dequantize with
    in-register, and ``selfs`` carries the native-precision self buffers —
    the local parameters never cross the wire, so they are mixed exactly at
    ``weights[..., 0]`` while only true neighbor payloads pay quantization
    noise.  Both are ``None`` for unquantized exchanges.  ``seed`` (an
    int32 scalar, typically the optimizer step) drives the stochastic
    rounding; it is decorrelated per bucket and per agent, identically in
    both execution modes, so stacked and sharded quantized trajectories
    match exactly whenever their bucket layouts coincide (params sharded
    over non-agent mesh axes pack differently per device, which draws the
    same seeds at different row positions).

    Phase stages (the StepProgram engine's pipeline, see
    :mod:`repro.core.engine`): ``gather`` is the one-shot sync form;
    ``quantize_stage(bufs, seed)`` and ``exchange_stage(wire, step)``
    expose the same computation as two separately schedulable halves.
    ``quantize_stage`` maps packed buckets to the **wire state** — one
    ``(payload, row_scales)`` pair per bucket, always carrying the leading
    agent axes so it can live inside the optimizer state under either
    execution mode (f32/bf16 wires carry unit scales).  ``exchange_stage``
    turns a wire state into the self-separated kernel operands
    ``(neighbor_stacks, weights_q, scale_stacks)`` with the self weight
    first — in the sharded mode this is where the ``ppermute``\\ s happen,
    and because the wire state may come from the *previous* optimizer step
    the exchange has no data dependency on the current backward (the
    ``schedule="overlap"`` one-step-stale pipeline).  ``step`` indexes the
    schedule of a time-varying strategy (ignored by fixed topologies).

    All three callables delegate to ``strategy`` — the
    :class:`MixingStrategy` object configured by ``program`` — which also
    carries the multi-round pipeline (``continue_from_wire``) and the
    error-feedback quantizer (``quantize_ef``) the engine schedules.
    """

    lead: int                     # leading replica axes excluded from packing
    batched: bool                 # True: stacked simulation (dense Pi vmap)
    gather: Callable              # (bufs, seed) -> (nbrs, weights, scales, selfs)
    interpret: Optional[bool] = None   # None: resolve_interpret (backend)
    exchange: str = "f32"         # wire precision: f32 | bf16 | int8 | fp8
    n_agents: int = 1
    # split phase stages (see class docstring); None on comms predating them
    quantize_stage: Optional[Callable] = None   # (bufs, seed) -> wire
    exchange_stage: Optional[Callable] = None   # (wire, step) -> (nbrs, weights_q, scales)
    # the mixing-strategy layer (None only on hand-rolled test comms)
    strategy: Optional["MixingStrategy"] = None
    program: Optional[MixingProgram] = None

    def spec(self, tree: PyTree) -> flatbuf.FlatSpec:
        return flatbuf.make_flat_spec(tree, lead=self.lead)

    def pack(self, tree: PyTree, spec: flatbuf.FlatSpec):
        bufs = flatbuf.pack(tree, spec)
        if not self.batched and self.lead:
            # sharded: the local agent axis is fully sharded away (size 1)
            for b in bufs:
                assert all(d == 1 for d in b.shape[:self.lead]), b.shape
            bufs = [b.reshape(b.shape[self.lead:]) for b in bufs]
        return bufs

    def unpack(self, bufs, spec: flatbuf.FlatSpec) -> PyTree:
        if not self.batched and self.lead:
            bufs = [b.reshape((1,) * self.lead + b.shape) for b in bufs]
        return flatbuf.unpack(bufs, spec)


# distinct odd strides decorrelate the stochastic-rounding streams across
# steps, buckets, agents, inner consensus rounds, and wire payloads
# (params vs mixed momentum) while keeping stacked/sharded seeds identical
# (without the step stride, step t+1 / bucket b would collide with step
# t+1-7919k / bucket b+k; int32 wraparound at large steps is fine — the
# seed only needs to be a well-spread hash input).  The composition is
# documented by :func:`wire_seed` and pinned collision-free over the
# realistic index ranges in tests/test_mixing.py.
_SEED_STEP_STRIDE = 1000003
_SEED_BUCKET_STRIDE = 7919
_SEED_AGENT_STRIDE = 104729
_SEED_ROUND_STRIDE = 611953
_SEED_PAYLOAD_STRIDE = 2750161


def wire_seed(step, agent: int = 0, bucket: int = 0, rnd: int = 0,
              payload: int = 0) -> int:
    """The SR-stream seed of one quantized wire payload, as a host int.

    This is THE seed composition both execution modes implement (the
    stacked mode vectorizes the agent term, the sharded mode derives it
    from ``lax.axis_index``):

        seed = STEP * (step + ROUND * rnd) + AGENT * agent
             + BUCKET * bucket + PAYLOAD * payload      (mod 2^32)

    ``rnd`` is the inner consensus round (0 = the round-1 wire, whose seed
    is the bare optimizer step); ``payload`` is 0 for params and 1 for the
    mixed momentum buffer.  Returns the signed int32 value the stages feed
    the quantizer (the traced arithmetic wraps identically).  Exposed so
    tests can assert the strides stay collision-free over the realistic
    index ranges by construction.
    """
    s = np.int64(step) + np.int64(_SEED_ROUND_STRIDE) * np.int64(rnd)
    seed = (np.int64(_SEED_STEP_STRIDE) * s
            + np.int64(_SEED_AGENT_STRIDE) * np.int64(agent)
            + np.int64(_SEED_BUCKET_STRIDE) * np.int64(bucket)
            + np.int64(_SEED_PAYLOAD_STRIDE) * np.int64(payload))
    return int(np.int64(seed).astype(np.int32))


def _check_exchange(exchange: str) -> str:
    """Fail at comm construction, not deep inside the first traced update."""
    if exchange not in flatbuf.EXCHANGE_DTYPES:
        raise ValueError(f"unknown exchange precision {exchange!r}; "
                         f"expected one of {flatbuf.EXCHANGE_DTYPES}")
    return exchange


def _wire_payload(buf, seed, exchange: str, interpret: Optional[bool]):
    """Cast/quantize one packed bucket for the wire -> (payload, scales).

    ``bf16`` casts the whole stencil *including* the self tile: without
    scales the kernels need one homogeneous neighbor operand, and the
    ~2^-8 relative rounding this adds to the self term is the mode's
    stated noise level anyway.  int8/fp8 keep self native (see ``selfs``).
    """
    if exchange == "f32":
        return buf, None
    if exchange == "bf16":
        return buf.astype(jnp.bfloat16), None
    from repro.kernels.consensus_update.consensus_update import sr_quantize_2d
    return sr_quantize_2d(buf, seed, exchange=exchange, interpret=interpret)


def _quantize_wire_stacked(bufs, seed, n: int, exchange: str,
                           interpret: Optional[bool], payload: int = 0):
    """Quantize agent-stacked ``(A, rows, 128)`` buckets for the wire.

    Returns the wire state: one ``(payload, (A, rows, 1) f32 scales)`` pair
    per bucket.  Per-agent seeds match the sharded stage's
    ``axis_index``-derived seeds, so both execution modes produce the same
    wire bits from the same parameters.  f32/bf16 wires cast and carry
    unit scales (the fused kernels' in-register dequant multiply is then
    the identity), so every exchange precision shares one wire layout.
    ``payload`` decorrelates the SR streams of the second payload tree
    (the mixed momentum buffer) from the params' — see :func:`wire_seed`.
    """
    if exchange in ("f32", "bf16"):
        return tuple(
            (_wire_payload(b, None, exchange, interpret)[0],
             jnp.ones(b.shape[:-1] + (1,), jnp.float32)) for b in bufs)
    base = _SEED_STEP_STRIDE * jnp.asarray(seed, jnp.int32) \
        + jnp.int32(_SEED_PAYLOAD_STRIDE * payload)
    agent_seeds = _SEED_AGENT_STRIDE * jnp.arange(n, dtype=jnp.int32)
    out = []
    for bi, b in enumerate(bufs):
        q, sc = jax.vmap(
            lambda x, s: _wire_payload(x, s, exchange, interpret)
        )(b, base + _SEED_BUCKET_STRIDE * bi + agent_seeds)
        out.append((q, sc))
    return tuple(out)


# --------------------------------------------------------------------------
# Compressed wire payloads (the biased EF-rail compressors)
# --------------------------------------------------------------------------


class TopKWire(NamedTuple):
    """Static-shape wire contract of one top-k-compressed bucket.

    The ragged ``ceil(p * n)`` selection is rounded up to a lane-aligned
    compact tile (:func:`repro.kernels.consensus_update.topk.topk_k_rows`),
    so every ppermute moves three fixed-shape arrays (a NamedTuple — i.e.
    a pytree — so checkpointing, PartitionSpecs, and the dependency-report
    labeling treat it as more wire leaves with zero special casing):

    * ``values``  — int8 ``(*lead, k_rows, 128)`` SR-quantized compact
      values;
    * ``indices`` — int32 ``(*lead, k_rows, 128)`` flat dense positions
      (``row * 128 + lane``);
    * ``scales``  — f32 ``(*lead, k_rows, 1)`` per-compact-row scales.

    Unlike the dense wire's locally synthesized unit scales, ALL three
    fields cross the wire (the receiver cannot reconstruct any of them),
    which the byte accounting prices accordingly.
    """

    values: Any
    indices: Any
    scales: Any


class RankWire(NamedTuple):
    """Wire contract of one rank-r-compressed bucket: two dense f32
    factors (``reconstruction = p @ qt``), both crossing the wire.

    * ``p``  — f32 ``(*lead, rows, r)`` orthonormal left factor;
    * ``qt`` — f32 ``(*lead, r, 128)`` right factor.

    The warm-start basis ``Q (128, r)`` is NOT part of the wire — it is
    local state carried in ``OptState.qwarm`` (like the EF residual, it
    never crosses the wire).
    """

    p: Any
    qt: Any


def _decompress_entry(entry, rows: int):
    """Compressed wire entry -> dense f32 bucket, any leading axes.

    Flattens every axis before the trailing two, maps the per-bucket
    decompressor, and restores the lead shape — the one gather-dequant
    form both execution modes (and the EF residual update) share.
    """
    from repro.kernels.consensus_update.topk import (
        rank_decompress_2d, topk_decompress_2d)

    if isinstance(entry, TopKWire):
        lead_shape = entry.values.shape[:-2]
        fn = lambda v, i, s: topk_decompress_2d(v, i, s, rows)
        args = (entry.values, entry.indices, entry.scales)
    elif isinstance(entry, RankWire):
        lead_shape = entry.p.shape[:-2]
        fn = rank_decompress_2d
        args = (entry.p, entry.qt)
    else:
        raise TypeError(f"not a compressed wire entry: {type(entry).__name__}")
    flat = [a.reshape((-1,) + a.shape[len(lead_shape):]) for a in args]
    out = jax.vmap(fn)(*flat)
    return out.reshape(lead_shape + out.shape[-2:])


def _is_compressed_entry(entry) -> bool:
    return isinstance(entry, (TopKWire, RankWire))


def _compress_wire_stacked(bufs, seed, n: int, program: MixingProgram,
                           interpret: Optional[bool], qwarm):
    """Compress agent-stacked ``(A, rows, 128)`` buckets for the wire.

    The compressed analog of :func:`_quantize_wire_stacked`: per-agent
    top-k value-SR seeds follow the SAME :func:`wire_seed` composition as
    the dense int8 wire (step/agent/bucket strides — the compact values
    are just a smaller int8 payload), so stacked and sharded trajectories
    match bit-for-bit.  Returns ``(wire, qwarm')`` where ``qwarm`` is the
    per-bucket ``(A, 128, r)`` warm-start stack of the rank compressor
    (``()`` in and out for top-k).
    """
    from repro.kernels.consensus_update import topk as tk

    kind, param = parse_compressor(program.compressor)
    if kind == "topk":
        base = _SEED_STEP_STRIDE * jnp.asarray(seed, jnp.int32)
        agent_seeds = _SEED_AGENT_STRIDE * jnp.arange(n, dtype=jnp.int32)
        out = []
        k_list = tk.topk_k_rows_for([b.shape[-2] for b in bufs], param)
        for bi, (b, k_rows) in enumerate(zip(bufs, k_list)):
            v, i, s = jax.vmap(
                lambda x, sd: tk.topk_compress_2d(x, k_rows, sd,
                                                  interpret=interpret)
            )(b.astype(jnp.float32), base + _SEED_BUCKET_STRIDE * bi
              + agent_seeds)
            out.append(TopKWire(values=v, indices=i, scales=s))
        return tuple(out), ()
    assert kind == "rank", kind
    wire, nq = [], []
    for b, q in zip(bufs, qwarm):
        p, qt, q2 = jax.vmap(tk.rank_compress_2d)(b.astype(jnp.float32), q)
        wire.append(RankWire(p=p, qt=qt))
        nq.append(q2)
    return tuple(wire), tuple(nq)


def _qwarm_init_stacked(bufs, n: int, program: MixingProgram):
    """Initial warm-start state: one ``(A, 128, r)`` orthonormal basis per
    bucket for the rank compressor, ``()`` otherwise (top-k is stateless
    beyond the EF residual)."""
    from repro.kernels.consensus_update.topk import rank_init_q

    kind, param = parse_compressor(program.compressor)
    if kind != "rank":
        return ()
    q0 = rank_init_q(param)
    return tuple(jnp.broadcast_to(q0, (n,) + q0.shape) + 0.0 for _ in bufs)


# --------------------------------------------------------------------------
# Bounded-staleness wire ring (fault-tolerant overlap schedule)
# --------------------------------------------------------------------------


class WireRing(NamedTuple):
    """Depth-``S`` generalization of the overlap schedule's wire state.

    Lives in ``OptState.wire`` exactly where the one-deep ``(payload,
    scales)`` tuple lives today (a NamedTuple, so checkpointing and the
    dependency-report labeling treat it as more wire leaves — bit-exact
    round-trips with zero checkpoint changes):

    * ``slots`` — one ``(payload, scales)`` pair per bucket x payload tree,
      with a ring axis inserted after the agent axis: ``(A, S, rows, 128)``
      stacked / ``(1, S, rows, 128)`` shard-local.  Ring index 0 is the
      agent's own freshest quantized generation (what the plain overlap
      wire carries), index ``k`` is ``k`` steps older.  Carried slots are
      never re-quantized — each generation keeps the SR bits it was born
      with, so stale consumption cannot collide with a live SR stream.
    * ``send_age`` — ``(A,)`` / ``(1,)`` int32: the ring index the agent
      *contributes* this step (its freshest generation that escaped the
      injected straggler delays; ``S`` = nothing within the ring arrived
      and receivers mask it out).  The sender selects ONE generation for
      all receivers, so the exchanged operand stays a single per-bucket
      stack and the existing self-separated kernels apply unchanged.
    * ``ages`` — ``(A, A)`` / ``(1, A)`` int32 bookkeeping: receiver row
      ``i``, the staleness-minus-1 of what sender ``j`` delivered (0 =
      normal one-step-stale; sentinel ``S`` = masked by drop/over-stale;
      diagonal 0 — the self term is always fresh).  Deterministic given
      the fault schedule; carried so checkpoints/dryruns expose the
      arrival state without re-deriving it.
    """

    slots: Tuple
    send_age: Any
    ages: Any


def _ring_select(ring: WireRing, staleness: int):
    """Sender-side slot selection: ring -> plain per-bucket wire pairs.

    Each agent contributes ``ring[min(send_age, S-1)]`` — its freshest
    arrived generation.  A fully masked sender (``send_age == S``) selects
    the oldest slot harmlessly: every receiver weights it zero.
    """
    sel = jnp.minimum(ring.send_age.astype(jnp.int32), staleness - 1)
    out = []
    for p, sc in ring.slots:
        idx = sel.reshape((-1,) + (1,) * (p.ndim - 1))
        out.append((jnp.take_along_axis(p, idx, axis=1)[:, 0],
                    jnp.take_along_axis(sc, idx, axis=1)[:, 0]))
    return tuple(out)


def _ring_push(old, new):
    """Shift one ring buffer: fresh generation in, oldest out."""
    return jnp.concatenate([new[:, None], old[:, :-1]], axis=1)


def _fault_tables(program: MixingProgram) -> dict:
    """Host-precomputed fault-path tables over the combined period.

    Everything the runtime indexes with ``step % period`` is a static
    numpy table baked into the jitted step — the fault layer adds zero
    collectives and zero device randomness, and both execution modes read
    the identical tables (:class:`~repro.core.faults.FaultSchedule` is
    seeded host-side like ``TopologySchedule``):

    * ``send_age (P, A)`` — steady state of the carried ``send_age``
      counter recurrence (valid because ``straggle[0]`` is all-False);
    * ``arrive (P, A, A)`` — receiver ``i`` uses sender ``j`` this step;
    * ``weights (P, A, A+1)`` — arrival-masked renormalized
      self-separated weights (:func:`repro.core.faults.arrival_masked_pi`
      of each schedule entry's ``Pi``);
    * ``ages (P, A, A)`` — the :class:`WireRing` bookkeeping rows.
    """
    s = program.staleness
    sched = program.schedule
    f = program.faults or trivial_faults(sched.n_agents)
    tb = f.tables(s)
    pw = int(np.lcm(sched.period, f.period))
    if pw > _MAX_FAULT_PERIOD:
        raise ValueError(
            f"combined schedule x fault period {pw} exceeds "
            f"{_MAX_FAULT_PERIOD}; align the fault period with the "
            "topology schedule period")
    ts = np.arange(pw)
    straggle = f.straggle[ts % f.period]
    send_age = tb["send_age"][ts % f.period]
    arrive = tb["arrive"][ts % f.period]
    weights = np.stack([
        _self_separated_weights(arrival_masked_pi(
            sched.topologies[t % sched.period].pi, arrive[t]))
        for t in range(pw)])
    ages = np.where(arrive, send_age[:, None, :], s).astype(np.int32)
    di = np.arange(sched.n_agents)
    ages[:, di, di] = 0
    return {"period": pw, "S": s, "straggle": straggle,
            "send_age": send_age, "arrive": arrive,
            "weights": weights, "ages": ages}


# --------------------------------------------------------------------------
# MixingStrategy: how the wire stages compose per optimizer step
# --------------------------------------------------------------------------


class MixingStrategy:
    """Base strategy: one consensus round of a (possibly step-indexed) Pi.

    Subclasses select behavior via ``rounds`` and ``_entry``; the heavy
    lifting lives in four execution-mode-specific primitives supplied by
    :func:`stacked_flat_comm` / :func:`sharded_flat_comm`:

    * ``quantize(bufs, seed) -> wire`` — packed buckets to wire state;
    * ``exchange_t(wire, t) -> (nbrs, weights_q, scales)`` — one round of
      neighbor exchange under schedule entry ``t`` (``None`` = entry 0,
      statically); in the sharded mode this is where the ``ppermute``\\ s
      (under ``lax.switch`` for time-varying schedules) happen;
    * ``combine(nbrs, weights_q, scales, selfs) -> bufs`` — the mixing sum
      in full precision, used *between* inner rounds (the final round is
      fused into the update kernel);
    * ``wire_to_bufs(wire) -> bufs_f32`` — local dequantization, used by
      the error-feedback residual update.

    The engine-facing entry points are :meth:`continue_from_wire` (rounds
    1..k given the round-1 wire — carried state under ``schedule="overlap"``,
    fresh under ``sync``) and :meth:`quantize_ef`.
    """

    name = "static"

    def __init__(self, program: MixingProgram, *, quantize, exchange_t,
                 combine, wire_to_bufs, legacy_gather=None,
                 bufs_to_state=None, state_to_bufs=None, fault_ops=None,
                 compress=None, qwarm_init=None, meta=None):
        self.program = program
        self.rounds = program.rounds
        self.mixed_momentum = program.momentum_mixing == "mixed"
        self.compressed = program.compressed
        self._quantize = quantize
        self._exchange_t = exchange_t
        self._combine = combine
        self._wire_to_bufs = wire_to_bufs
        self._legacy_gather = legacy_gather
        # biased-compressor primitives (topk/rank programs only):
        # compress(bufs, seed, qwarm) -> (wire, qwarm'), and the
        # qwarm initializer; the shared ``meta`` dict carries the static
        # dense bucket row counts the decompressors need (set on every
        # bufs-seeing call — the compact top-k payload alone cannot
        # recover the dense shape)
        self._compress = compress
        self._qwarm_init = qwarm_init
        self._meta = meta if meta is not None else {}
        # execution-mode-specific fault-path closures (None = fault-free;
        # see stacked_flat_comm / sharded_flat_comm): masked_weights(t),
        # own_straggle(t), next_ages(t), init_state(), period, S
        self.fault_ops = fault_ops
        # residual buffers live in the optimizer state with the leading
        # agent axes kept (like the wire pairs) so sharded PartitionSpecs
        # apply; the sharded mode's packed bufs are squeezed, so these two
        # convert between the layouts (identity in the stacked mode).
        ident = lambda bufs: list(bufs)
        self._bufs_to_state = bufs_to_state or ident
        self._state_to_bufs = state_to_bufs or ident

    # -- schedule indexing --------------------------------------------------
    def _entry(self, step):
        """Schedule entry for optimizer step ``step`` (None = static 0)."""
        return None

    # -- static bucket-shape bookkeeping (compressed programs) --------------
    def _note_bufs(self, bufs):
        """Record the static dense row counts the decompressors need.

        Called on every path that sees the packed buckets *before* an
        exchange can run (quantize/compress, ``continue_from_wire``,
        residual/qwarm init) — the values are static ints fixed by the
        comm's bucket layout, so re-recording is idempotent."""
        if self.compressed:
            self._meta["rows"] = [int(b.shape[-2]) for b in bufs]

    # -- payload splitting (momentum_mixing="mixed") ------------------------
    def _quantize_payloads(self, bufs, seed):
        """Quantize the wire payload(s): params, plus the mixed momentum.

        With ``momentum_mixing="mixed"`` every bucket list the strategy
        sees is the concatenation ``params_bufs + momentum_bufs`` (equal
        halves — momentum mirrors the param spec); the momentum half draws
        its SR streams with the payload seed stride so the two payloads'
        rounding noise stays independent (see :func:`wire_seed`).
        """
        if not self.mixed_momentum:
            return tuple(self._quantize(bufs, seed))
        b = len(bufs) // 2
        assert len(bufs) == 2 * b, len(bufs)
        return (tuple(self._quantize(bufs[:b], seed))
                + tuple(self._quantize(bufs[b:], seed, payload=1)))

    # -- the FlatComm stage contract ---------------------------------------
    def quantize_stage(self, bufs, seed):
        if self.compressed:
            # reachable only from initial_wire (the x_{-1} := x_0 priming):
            # the per-step compressions all go through compress_ef — a
            # biased compressor without EF is rejected at config time.
            # The warm start consumed here is the deterministic init basis;
            # OptState.qwarm starts from the same basis, so step 0 re-runs
            # the iteration one step less warm (a quality ramp, not a
            # correctness dependency).
            self._note_bufs(bufs)
            wire, _ = self._compress(bufs, seed, self._qwarm_init(bufs))
            return wire
        return self._quantize_payloads(bufs, seed)

    def exchange_stage(self, wire, step=None):
        """One round of neighbor exchange; fault-aware when engaged.

        On the fault path ``wire`` is either the carried :class:`WireRing`
        (round 1 — the sender-selected slot is exchanged) or a freshly
        quantized plain tuple (inner multi-round rounds — a masked sender's
        live transmissions miss the whole step, so the same per-step
        arrival mask applies); either way the schedule's weights are
        replaced by the arrival-masked renormalized row(s), which is the
        *only* thing that changes about the exchanged operands — same
        ppermutes, same shapes, same kernels.
        """
        if self.fault_ops is None:
            return self._exchange_t(wire, self._entry(step))
        fo = self.fault_ops
        if step is None:
            raise ValueError("fault-tolerant mixing needs the optimizer "
                             "step; exchange_stage(wire, step)")
        t = jnp.mod(jnp.asarray(step, jnp.int32), fo["period"])
        if isinstance(wire, WireRing):
            wire = _ring_select(wire, fo["S"])
        nbrs, _w, scs = self._exchange_t(wire, self._entry(step))
        return nbrs, fo["masked_weights"](t), scs

    def combine(self, nbrs, weights_q, scales, selfs):
        return self._combine(nbrs, weights_q, scales, selfs)

    # -- carried wire state (schedule="overlap") ----------------------------
    def advance_wire(self, bufs, old_wire, step):
        """Produce the wire state step ``step + 1`` will consume.

        Fault-free: exactly today's double-buffer — quantize the current
        buckets, drop the old wire.  Fault path: push the fresh generation
        into the :class:`WireRing` and advance the age counters by the
        recurrence whose steady state is the precomputed ``send_age``
        table (``a' = min(a + 1, S)`` while straggling, else 0) — asserted
        equal in tests, and load-bearing for the sender's slot selection.
        """
        fresh = self.quantize_stage(bufs, step)
        if self.fault_ops is None:
            return fresh
        fo = self.fault_ops
        slots = tuple((_ring_push(op, p), _ring_push(osc, sc))
                      for (op, osc), (p, sc) in zip(old_wire.slots, fresh))
        t1 = jnp.mod(jnp.asarray(step, jnp.int32) + 1, fo["period"])
        send_age = jnp.where(
            fo["own_straggle"](t1),
            jnp.minimum(old_wire.send_age + 1, fo["S"]),
            0).astype(jnp.int32)
        return WireRing(slots=slots, send_age=send_age,
                        ages=fo["next_ages"](t1))

    def initial_wire(self, bufs):
        """Wire state priming step 0 (the ``x_{-1} := x_0`` convention).

        Fault path: the seed ``-1`` generation *replicated* across the ring
        slots (replication, not re-quantization — one SR draw, copied), so
        whichever slot a straggler schedule selects early on carries the
        same bits today's overlap init would.  ``send_age`` starts 0 for
        everyone: ``straggle[0]`` is all-False by construction ("step 0
        publishes"), so the counters match the steady-state tables from
        the very first step.
        """
        wire = self.quantize_stage(bufs, jnp.int32(-1))
        if self.fault_ops is None:
            return wire
        fo = self.fault_ops
        slots = tuple((jnp.repeat(p[:, None], fo["S"], axis=1),
                       jnp.repeat(sc[:, None], fo["S"], axis=1))
                      for p, sc in wire)
        send_age, ages = fo["init_state"]()
        return WireRing(slots=slots, send_age=send_age, ages=ages)

    def continue_from_wire(self, bufs, wire, step):
        """Rounds 1..k of the per-step pipeline, round 1 from ``wire``.

        ``wire`` is either the freshly quantized current params (sync) or
        the carried one-step-stale buffer (overlap — only round 1 consumes
        it; rounds 2..k re-quantize the partially mixed buffers and stay on
        the grad->update critical path).  Returns the final round's kernel
        operands ``(nbrs, weights, scales, selfs)`` where ``selfs`` is the
        round-(k-1) mixed buffer (the fused kernel applies round k +
        gradient in one launch).  Inner rounds run under ``lax.scan``.
        """
        self._note_bufs(bufs)
        nbrs, w, sc = self.exchange_stage(wire, step)
        if self.rounds == 1:
            return nbrs, w, sc, list(bufs)
        b = self._combine(nbrs, w, sc, bufs)              # round 1
        if self.rounds > 2:
            step_i = jnp.asarray(step, jnp.int32)
            seeds = step_i + _SEED_ROUND_STRIDE * jnp.arange(
                1, self.rounds - 1, dtype=jnp.int32)

            def round_body(carry, seed_r):
                wire_r = self._quantize_payloads(list(carry), seed_r)
                nb, wr, scr = self.exchange_stage(wire_r, step)
                return tuple(self._combine(nb, wr, scr, list(carry))), None

            b, _ = lax.scan(round_body, tuple(b), seeds)
            b = list(b)
        seed_k = jnp.asarray(step, jnp.int32) + \
            _SEED_ROUND_STRIDE * (self.rounds - 1)
        wire_k = self._quantize_payloads(b, seed_k)
        nbrs, w, sc = self.exchange_stage(wire_k, step)
        return nbrs, w, sc, list(b)

    def gather(self, bufs, seed):
        """One-shot sync form: quantize current params, run all rounds."""
        if self._legacy_gather is not None and self.program.is_trivial:
            # bit-for-bit the pre-strategy path (incl. the dense-weight
            # unquantized stacked form)
            return self._legacy_gather(bufs, seed)
        wire = self._quantize_payloads(bufs, seed)
        return self.continue_from_wire(bufs, wire, seed)

    # -- error feedback -----------------------------------------------------
    def quantize_ef(self, bufs, seed, residual):
        """EF-compress the round-1 wire payload: ``Q(x + e)``.

        Returns ``(wire, new_residual)`` with ``new_residual = (x + e) -
        dequant(Q(x + e))`` — the compression error carried to the next
        step so quantization noise telescopes instead of accumulating
        (Seide et al. 2014 / Karimireddy et al. 2019).  The residual is
        f32, never crosses the wire, and applies to the round-1 payload(s)
        only; inner multi-round payloads are fresh each step and use plain
        stochastic rounding.  With ``momentum_mixing="mixed"`` the
        residual list has one buffer per bucket per payload (params first,
        momentum second) and each payload's compression error telescopes
        independently.
        """
        res = self._state_to_bufs(residual)
        carried = [b.astype(jnp.float32) + e for b, e in zip(bufs, res)]
        wire = self._quantize_payloads(carried, seed)
        deq = self._wire_to_bufs(wire)
        new_residual = tuple(self._bufs_to_state(
            [c - d for c, d in zip(carried, deq)]))
        return wire, new_residual

    def compress_ef(self, bufs, seed, residual, qwarm):
        """The compressor-axis generalization of :meth:`quantize_ef`.

        ``C(x + e)`` for whatever compressor the program carries, threading
        the warm-start state of the rank compressor: returns ``(wire,
        new_residual, new_qwarm)``.  Dense programs delegate to
        :meth:`quantize_ef` and pass ``qwarm`` through untouched, so the
        engine calls this unconditionally at both EF sites.  For the biased
        compressors the residual update uses the same gather-dequant
        decompression the receivers apply — ``new_residual = (x + e) -
        decompress(C(x + e))`` — which is exactly what makes the
        delta-contraction of the EF bound hold
        (:func:`repro.core.lyapunov.ef_compressed_consensus_bound`).
        """
        if not self.compressed:
            wire, new_residual = self.quantize_ef(bufs, seed, residual)
            return wire, new_residual, qwarm
        self._note_bufs(bufs)
        res = self._state_to_bufs(residual)
        carried = [b.astype(jnp.float32) + e for b, e in zip(bufs, res)]
        wire, new_qwarm = self._compress(carried, seed, qwarm)
        deq = self._wire_to_bufs(wire)
        new_residual = tuple(self._bufs_to_state(
            [c - d for c, d in zip(carried, deq)]))
        return wire, new_residual, new_qwarm

    def residual_init(self, bufs):
        """Zero-initialized f32 residuals, one per packed bucket (leading
        agent axes kept, matching the wire state's layout)."""
        self._note_bufs(bufs)
        return tuple(self._bufs_to_state(
            [jnp.zeros(b.shape, jnp.float32) for b in bufs]))

    def qwarm_init(self, bufs):
        """Initial compressor warm-start state for ``OptState.qwarm``:
        the rank compressor's per-bucket orthonormal basis (leading agent
        axes kept, like the wire/residual), ``()`` for everything else."""
        if not self.compressed:
            return ()
        self._note_bufs(bufs)
        return self._qwarm_init(bufs)

    # -- wire-byte pricing (the single accounting source) -------------------
    def bytes_per_neighbor(self, spec: "flatbuf.FlatSpec") -> int:
        """Bytes ONE whole-model neighbor transfer moves under this
        program — dense, quantized, and compressed payloads priced in one
        place (:func:`program_bytes_per_neighbor`); `exchange_bytes_per_
        step`, the trainer/dryrun printouts, and the microbench all quote
        this, and ``repro.core.engine.wire_bytes_per_neighbor`` asserts it
        against the actual carried buffers."""
        return program_bytes_per_neighbor(spec, self.program)


class StaticMixing(MixingStrategy):
    """The paper's fixed ``Pi``, one round — bit-for-bit the legacy path."""

    name = "static"


class TimeVaryingMixing(MixingStrategy):
    """``Pi_t = schedule[t % period]`` selected by the optimizer step.

    Stacked mode: the dense self-separated weights are indexed out of a
    ``(T, A, A+1)`` stack.  Sharded mode: each entry's circulant shift set
    is its own ``lax.switch`` branch of ``ppermute``\\ s (padded to the
    union stencil with zero-weight slots), so a step only pays its own
    entry's collectives.
    """

    name = "time_varying"

    def __init__(self, program, **kw):
        super().__init__(program, **kw)
        self._period = program.schedule.period

    def _entry(self, step):
        if step is None:
            raise ValueError("TimeVaryingMixing needs the optimizer step to "
                             "select Pi_t; exchange_stage(wire, step)")
        return jnp.mod(jnp.asarray(step, jnp.int32), self._period)


class MultiRoundMixing(MixingStrategy):
    """``rounds`` inner consensus rounds per gradient step (i-CDSGD).

    ``x' = Pi^k x - alpha g``: rounds 1..k-1 mix in full precision between
    re-quantizations (``lax.scan``), round k is fused into the update
    kernel.  Wire cost is exactly ``k x`` the single-round bytes.
    ``MultiRoundMixing`` with ``rounds=1`` is never constructed — the
    factories return :class:`StaticMixing` (identical by definition).
    """

    name = "multi_round"


def _make_strategy(program: MixingProgram, **prims) -> MixingStrategy:
    if program.strategy == "time_varying":
        return TimeVaryingMixing(program, **prims)
    if program.strategy == "multi_round" and program.rounds > 1:
        return MultiRoundMixing(program, **prims)
    return StaticMixing(program, **prims)


def _self_separated_weights(pi: np.ndarray) -> np.ndarray:
    """``[diag(Pi) | zero-diag Pi]`` — the quantized-form (A, A+1) weights."""
    n = pi.shape[0]
    pi = np.asarray(pi, np.float64)
    return np.concatenate([np.diag(pi)[:, None],
                           pi * (1.0 - np.eye(n))], axis=1)


def stacked_flat_comm(topology: Topology, *, interpret: Optional[bool] = None,
                      exchange: str = "f32",
                      program: Optional[MixingProgram] = None) -> FlatComm:
    """FlatComm for agent-stacked pytrees (dense ``Pi``, any topology).

    Quantized exchanges quantize the agent stack once (per-agent seeds
    matching the sharded path's ``axis_index``-derived seeds) and return
    the native-precision stack as ``selfs``: agent ``j`` mixes its own
    exact parameters at ``weights[j, 0] = Pi[j, j]`` and the dequantized
    wire payloads of everyone else (``weights[j, 1:] = Pi[j, :]`` with the
    diagonal zeroed) — exactly what the sharded exchange delivers, where
    the self buffer never crosses the wire.

    ``program`` selects the mixing strategy (default: the trivial static
    program over ``topology``); its schedule entries supply the per-step
    ``Pi_t`` of a time-varying strategy.
    """
    if program is None:
        program = make_mixing_program(topology, exchange=exchange)
    exchange = _check_exchange(program.exchange)
    schedule = program.schedule
    pi = jnp.asarray(schedule.topologies[0].pi, dtype=jnp.float32)
    n = schedule.n_agents
    # quantized-form weights per schedule entry: [diag | off-diag], (T, A, A+1)
    pi_q_stack = jnp.asarray(
        np.stack([_self_separated_weights(t.pi) for t in schedule.topologies]),
        jnp.float32)
    period = schedule.period

    meta: dict = {}

    def _rows_of(bi: int) -> int:
        rows = meta.get("rows")
        if rows is None:
            raise RuntimeError(
                "compressed exchange before any bufs-seeing stage: call "
                "quantize_stage/compress_ef (or continue_from_wire) once so "
                "the strategy records the dense bucket row counts")
        return rows[bi]

    def quantize(bufs, seed, payload=0):
        return _quantize_wire_stacked(bufs, seed, n, exchange, interpret,
                                      payload=payload)

    def compress(bufs, seed, qwarm):
        return _compress_wire_stacked(bufs, seed, n, program, interpret,
                                      qwarm)

    def qwarm_init(bufs):
        return _qwarm_init_stacked(bufs, n, program)

    def exchange_t(wire, t):
        # stacked simulation: every agent already sees the full stack — the
        # "exchange" is handing the wire payloads to the kernels with the
        # self-separated [diag(Pi_t) | zero-diag Pi_t] weights.  Compressed
        # entries decompress to dense f32 stacks with unit scales (the
        # kernels' in-register dequant multiply becomes the identity) and
        # feed the same self-separated path — the self term never crossed
        # the wire and stays full precision at weights[..., 0].
        if t is None or period == 1:
            w = pi_q_stack[0]
        else:
            w = jnp.take(pi_q_stack, t, axis=0)
        nbrs, scs = [], []
        for bi, e in enumerate(wire):
            if isinstance(e, TopKWire) and program.sparse_update:
                # sparse operand form: hand the compact wire fields to the
                # *_update_sparse_2d kernels untouched — no dense
                # decompressed stack is ever materialized.  scales ride
                # inside the SparseNeighbors tuple (scs entry None).
                from repro.kernels.consensus_update.ops import SparseNeighbors
                nbrs.append(SparseNeighbors(e.values, e.indices, e.scales))
                scs.append(None)
            elif _is_compressed_entry(e):
                d = _decompress_entry(e, _rows_of(bi))
                nbrs.append(d)
                scs.append(jnp.ones(d.shape[:-1] + (1,), jnp.float32))
            else:
                nbrs.append(e[0])
                scs.append(e[1])
        return nbrs, w, scs

    def wire_to_bufs(wire):
        return [_decompress_entry(e, _rows_of(bi)) if _is_compressed_entry(e)
                else e[0].astype(jnp.float32) * e[1]
                for bi, e in enumerate(wire)]

    def combine(nbrs, weights_q, scales, selfs):
        """Full-precision one-round mix of the agent stack (inner rounds).

        ``mixed_j = w[j,0] self_j + sum_l w[j,1+l] dequant(payload_l)`` —
        the same sum the fused kernels evaluate, materialized because the
        next round re-quantizes it.
        """
        out = []
        for p, sc, sf in zip(nbrs, scales, selfs):
            deq = p.astype(jnp.float32) * sc              # (A, rows, 128)
            mixed = jnp.einsum("jl,lrc->jrc", weights_q[:, 1:], deq)
            mixed = mixed + weights_q[:, :1, None] * sf.astype(jnp.float32)
            out.append(mixed.astype(sf.dtype))
        return out

    def legacy_gather(bufs, seed):
        if exchange in ("f32", "bf16"):
            return ([_wire_payload(b, None, exchange, interpret)[0] for b in bufs],
                    pi, [None] * len(bufs), [None] * len(bufs))
        nbrs, w, scales = exchange_t(quantize(bufs, seed), None)
        return nbrs, w, scales, list(bufs)

    fault_ops = None
    if program.fault_tolerant:
        ft = _fault_tables(program)
        w_masked = jnp.asarray(ft["weights"], jnp.float32)    # (P, A, A+1)
        straggle_t = jnp.asarray(ft["straggle"])              # (P, A) bool
        ages_t = jnp.asarray(ft["ages"], jnp.int32)           # (P, A, A)
        fault_ops = {
            "period": ft["period"], "S": ft["S"],
            "masked_weights": lambda t: jnp.take(w_masked, t, axis=0),
            "own_straggle": lambda t: jnp.take(straggle_t, t, axis=0),
            "next_ages": lambda t: jnp.take(ages_t, t, axis=0),
            "init_state": lambda: (jnp.zeros((n,), jnp.int32), ages_t[0]),
        }

    strategy = _make_strategy(program, quantize=quantize, exchange_t=exchange_t,
                              combine=combine, wire_to_bufs=wire_to_bufs,
                              legacy_gather=legacy_gather, fault_ops=fault_ops,
                              compress=compress, qwarm_init=qwarm_init,
                              meta=meta)

    return FlatComm(lead=1, batched=True, gather=strategy.gather,
                    interpret=interpret, exchange=exchange, n_agents=n,
                    quantize_stage=strategy.quantize_stage,
                    exchange_stage=strategy.exchange_stage,
                    strategy=strategy, program=program)


def sharded_flat_comm(factors: Sequence[Tuple[str, Topology]], *,
                      lead: int = 1, interpret: Optional[bool] = None,
                      exchange: str = "f32",
                      program: Optional[MixingProgram] = None) -> FlatComm:
    """FlatComm for use inside ``shard_map``; circulant topologies only.

    ``factors`` is ``[(axis_name, Topology), ...]`` — one entry for the
    plain single-axis agent mesh, several for a Kronecker-factored one.
    Each bucket costs one ``lax.ppermute`` per non-zero shift combination;
    weights are the (outer-)product of the per-factor circulant weights.

    With a quantized ``exchange`` each agent quantizes its bucket ONCE and
    every non-identity shift permutes the int8/fp8 payload plus its
    ``(rows, 1)`` row scales — ~3.9x fewer bytes per shift than the f32
    wire; the self term (the identity shift) stays in native precision
    since it moves no data.

    A time-varying ``program`` (single agent axis only) compiles one
    ``lax.switch`` branch per schedule entry: branch ``t`` issues only
    entry ``t``'s circulant ``ppermute``\\ s, padding the neighbor stack to
    the union stencil with zero slots (whose weights are zero in that
    entry's weight row).
    """
    import itertools

    if program is not None:
        exchange = program.exchange
    _check_exchange(exchange)

    def _axis_data(per_factor):
        """[(axis, n, sorted shift items)] for one schedule entry."""
        out = []
        for axis_name, topo in per_factor:
            if topo.n_agents == 1:
                continue
            shifts = topo.shift_weights()
            if shifts is None:
                raise ValueError(
                    f"topology {topo.name!r} on axis {axis_name!r} is not "
                    "circulant; use mixing='ppermute' or 'dense' instead")
            out.append((axis_name, topo.n_agents, sorted(shifts.items())))
        return out

    time_varying = program is not None and program.strategy == "time_varying"
    if time_varying:
        live = [(a, t) for a, t in factors if t.n_agents > 1]
        if len(live) != 1:
            raise ValueError(
                "time-varying mixing supports a single agent mesh axis "
                f"(got {[a for a, _ in factors]}); factored multi-axis "
                "meshes need per-axis schedules, which are not implemented")
        axis_name = live[0][0]
        entries = [_axis_data([(axis_name, t)])
                   for t in program.schedule.topologies]
    else:
        entries = [_axis_data(factors)]

    # per-entry shift combinations; the wire stencil is their union so every
    # schedule entry returns identically shaped operands (lax.switch).
    def _combos(per_axis):
        return list(itertools.product(*[s for _, _, s in per_axis])) or [()]

    def _combo_weight(combo):
        return float(np.prod([w for _, w in combo]) if combo else 1.0)

    def _is_identity(per_axis, combo):
        return all(s % nn == 0 for (_, nn, _), (s, _w) in zip(per_axis, combo))

    def _combo_key(per_axis, combo):
        return tuple((ax, s % nn) for (ax, nn, _), (s, _w)
                     in zip(per_axis, combo))

    # union stencil over entries, keyed by (axis, shift mod n)
    union_keys: list = []
    entry_wire: list = []      # per entry: {key: (per_axis_index->shift, weight)}
    entry_selfw: list = []
    for per_axis in entries:
        wire_map = {}
        selfw = 0.0
        for c in _combos(per_axis):
            if _is_identity(per_axis, c):
                selfw += _combo_weight(c)
            else:
                k = _combo_key(per_axis, c)
                wire_map[k] = (per_axis, c, _combo_weight(c))
                if k not in union_keys:
                    union_keys.append(k)
        entry_wire.append(wire_map)
        entry_selfw.append(selfw)
    union_keys = sorted(union_keys)

    # (T, 1 + U) self-separated weights; zero where an entry lacks a shift
    weights_q_stack = jnp.asarray(
        [[sw] + [wm[k][2] if k in wm else 0.0 for k in union_keys]
         for sw, wm in zip(entry_selfw, entry_wire)], jnp.float32)

    # legacy single-entry views (static path keeps today's exact layout)
    per_axis0 = entries[0]
    combos0 = _combos(per_axis0)
    weights = jnp.asarray([_combo_weight(c) for c in combos0], jnp.float32)
    wire_combos0 = [c for c in combos0 if not _is_identity(per_axis0, c)]
    weights_q = weights_q_stack[0]

    def _agent_index():
        """Linearized agent index — matches the stacked topology order."""
        idx = jnp.int32(0)
        for axis_name, nn, _ in per_axis0:
            idx = idx * nn + lax.axis_index(axis_name).astype(jnp.int32)
        return idx

    def _shift_all(x, per_axis, combo):
        for (axis_name, nn, _), (s, _w) in zip(per_axis, combo):
            if s % nn:
                # agent j receives from agent (j + s) mod n
                perm = [((j + s) % nn, j) for j in range(nn)]
                x = lax.ppermute(x, axis_name, perm=perm)
        return x

    quantized = exchange in ("int8", "fp8") and union_keys
    n_total = int(np.prod([t.n_agents for _, t in factors])) if factors else 1

    meta: dict = {}

    def _rows_of(bi: int) -> int:
        rows = meta.get("rows")
        if rows is None:
            raise RuntimeError(
                "compressed exchange before any bufs-seeing stage: call "
                "quantize_stage/compress_ef (or continue_from_wire) once so "
                "the strategy records the dense bucket row counts")
        return rows[bi]

    def _restore_lead(a):
        return a.reshape((1,) * lead + a.shape)

    def compress(bufs, seed, qwarm):
        """Local squeezed buckets -> compressed wire entries (lead axes
        restored, like ``quantize``).  Top-k value-SR seeds derive from
        ``lax.axis_index`` with the same :func:`wire_seed` composition as
        the stacked path; the rank factors draw no randomness."""
        from repro.kernels.consensus_update import topk as tk

        kind, param = parse_compressor(program.compressor)
        if kind == "topk":
            base = _SEED_STEP_STRIDE * jnp.asarray(seed, jnp.int32) \
                + _SEED_AGENT_STRIDE * _agent_index()
            out = []
            k_list = tk.topk_k_rows_for([b.shape[-2] for b in bufs], param)
            for bi, (b, k_rows) in enumerate(zip(bufs, k_list)):
                v, i, s = tk.topk_compress_2d(
                    b.astype(jnp.float32), k_rows,
                    base + _SEED_BUCKET_STRIDE * bi, interpret=interpret)
                out.append(TopKWire(values=_restore_lead(v),
                                    indices=_restore_lead(i),
                                    scales=_restore_lead(s)))
            return tuple(out), ()
        assert kind == "rank", kind
        wire, nq = [], []
        for b, q in zip(bufs, qwarm):
            p, qt, q2 = tk.rank_compress_2d(b.astype(jnp.float32),
                                            q.reshape(q.shape[lead:]))
            wire.append(RankWire(p=_restore_lead(p), qt=_restore_lead(qt)))
            nq.append(_restore_lead(q2))
        return tuple(wire), tuple(nq)

    def qwarm_init(bufs):
        from repro.kernels.consensus_update.topk import rank_init_q

        kind, param = parse_compressor(program.compressor)
        if kind != "rank":
            return ()
        q0 = rank_init_q(param)
        return tuple(_restore_lead(q0) for _ in bufs)

    def quantize(bufs, seed, payload=0):
        """Local squeezed buckets -> wire state (lead axes restored).

        Runs inside ``shard_map``: the returned pairs carry the size-1
        local agent axes so the wire state round-trips through sharded
        optimizer-state PartitionSpecs unchanged.  ``payload`` selects the
        SR stream of the second (mixed momentum) payload tree.
        """
        base = _SEED_STEP_STRIDE * jnp.asarray(seed, jnp.int32) \
            + jnp.int32(_SEED_PAYLOAD_STRIDE * payload)
        if exchange in ("int8", "fp8"):
            base = base + _SEED_AGENT_STRIDE * _agent_index()
        out = []
        for bi, b in enumerate(bufs):
            if exchange in ("int8", "fp8"):
                p, sc = _wire_payload(b, base + _SEED_BUCKET_STRIDE * bi,
                                      exchange, interpret)
            else:
                p, _ = _wire_payload(b, None, exchange, interpret)
                sc = jnp.ones(b.shape[:-1] + (1,), jnp.float32)
            out.append((p.reshape((1,) * lead + p.shape),
                        sc.reshape((1,) * lead + sc.shape)))
        return tuple(out)

    def _entry_branch(entry_idx: int):
        """Exchange branch for one schedule entry: its own ppermutes only,
        padded to the union stencil with zero slots.

        Compressed entries (:class:`TopKWire` / :class:`RankWire`) shift
        every compact field through the SAME ppermutes — the only arrays
        that cross the wire are the compact payloads — then decompress
        per arrived stencil slot into a dense f32 neighbor tile with unit
        scales, feeding the fused kernels' self-separated path unchanged.
        """
        wm = entry_wire[entry_idx]

        def branch(wire):
            from repro.kernels.consensus_update.ops import SparseNeighbors

            nbrs, scs = [], []
            for bi, e in enumerate(wire):
                if isinstance(e, TopKWire) and program.sparse_update:
                    # sparse operand form: the ppermuted compact fields
                    # feed the *_update_sparse_2d kernels unchanged; an
                    # absent union slot ships all-zero values (dequant 0.0
                    # — and its weight is zero in this entry's row anyway)
                    local = jax.tree.map(
                        lambda a: a.reshape(a.shape[lead:]), e)
                    slots = []
                    for k in union_keys:
                        if k in wm:
                            per_axis, combo, _w = wm[k]
                            slots.append(jax.tree.map(
                                lambda a: _shift_all(a, per_axis, combo),
                                local))
                        else:
                            slots.append(jax.tree.map(jnp.zeros_like, local))
                    nbrs.append(SparseNeighbors(
                        *(jnp.stack([getattr(s, f) for s in slots])
                          for f in SparseNeighbors._fields)))
                    scs.append(None)
                    continue
                if _is_compressed_entry(e):
                    rows = _rows_of(bi)
                    local = jax.tree.map(
                        lambda a: a.reshape(a.shape[lead:]), e)
                    stack = []
                    for k in union_keys:
                        if k in wm:
                            per_axis, combo, _w = wm[k]
                            shifted = jax.tree.map(
                                lambda a: _shift_all(a, per_axis, combo),
                                local)
                            stack.append(_decompress_entry(shifted, rows))
                        else:
                            stack.append(
                                jnp.zeros((rows, flatbuf.LANE), jnp.float32))
                    nbrs.append(jnp.stack(stack))
                    scs.append(jnp.ones((len(union_keys), rows, 1),
                                        jnp.float32))
                    continue
                p, sc = e
                p = p.reshape(p.shape[lead:])
                sc = sc.reshape(sc.shape[lead:])
                stack, sstack = [], []
                for k in union_keys:
                    if k in wm:
                        per_axis, combo, _w = wm[k]
                        stack.append(_shift_all(p, per_axis, combo))
                        sstack.append(_shift_all(sc, per_axis, combo)
                                      if quantized else sc)
                    else:
                        stack.append(jnp.zeros_like(p))
                        sstack.append(jnp.zeros_like(sc) if quantized else sc)
                nbrs.append(jnp.stack(stack))
                scs.append(jnp.stack(sstack))
            return tuple(nbrs), tuple(scs)

        return branch

    branches = [_entry_branch(i) for i in range(len(entries))]

    def exchange_t(wire, t):
        """Wire state -> (neighbor stacks, weights_q, scale stacks).

        One ``lax.ppermute`` per non-identity shift combination for the
        payload, plus one for the row scales when the wire is quantized
        (f32/bf16 wires carry unit scales, which are shift-invariant — the
        kernels' dequant operand is synthesized locally, no collective);
        the self term never moves.  The wire may be one optimizer step
        stale (``schedule="overlap"``) — nothing here reads the current
        params or gradients.  ``t`` (traced) switches between the schedule
        entries' shift sets; ``None`` / period 1 runs entry 0 directly.
        """
        if not union_keys:
            raise ValueError("exchange_stage needs at least one wire-crossing "
                             "shift (topology has no neighbors)")
        if t is None or len(entries) == 1:
            nbrs, scs = branches[0](wire)
            return list(nbrs), weights_q, list(scs)
        t = jnp.asarray(t, jnp.int32)
        nbrs, scs = lax.switch(t, branches, wire)
        return list(nbrs), jnp.take(weights_q_stack, t, axis=0), list(scs)

    def wire_to_bufs(wire):
        out = []
        for bi, e in enumerate(wire):
            if _is_compressed_entry(e):
                local = jax.tree.map(lambda a: a.reshape(a.shape[lead:]), e)
                out.append(_decompress_entry(local, _rows_of(bi)))
            else:
                p, sc = e
                out.append(p.reshape(p.shape[lead:]).astype(jnp.float32)
                           * sc.reshape(sc.shape[lead:]))
        return out

    def bufs_to_state(bufs):
        return [b.reshape((1,) * lead + b.shape) for b in bufs]

    def state_to_bufs(state):
        return [b.reshape(b.shape[lead:]) for b in state]

    def combine(nbrs, w, scs, selfs):
        """Full-precision one-round mix of the local shard (inner rounds)."""
        out = []
        for p, sc, sf in zip(nbrs, scs, selfs):
            deq = p.astype(jnp.float32) * sc              # (U, rows, 128)
            mixed = jnp.tensordot(w[1:], deq, axes=1)
            mixed = mixed + w[0] * sf.astype(jnp.float32)
            out.append(mixed.astype(sf.dtype))
        return out

    def legacy_gather(bufs, seed):
        if not (quantized and wire_combos0):
            stacked = []
            for b in bufs:
                payload, _ = _wire_payload(b, None, exchange if exchange == "bf16"
                                           else "f32", interpret)
                stacked.append(jnp.stack(
                    [_shift_all(payload, per_axis0, c) for c in combos0]))
            return stacked, weights, [None] * len(bufs), [None] * len(bufs)
        nbrs, w, scs = exchange_t(quantize(bufs, seed), None)
        return nbrs, w, scs, list(bufs)

    if program is None:
        program = make_mixing_program(
            factors[0][1] if len(factors) == 1 else
            Topology(name="factored", pi=_factored_pi(factors)),
            exchange=exchange)

    fault_ops = None
    if program.fault_tolerant:
        live = [(a, t) for a, t in factors if t.n_agents > 1]
        if len(live) != 1:
            raise ValueError(
                "fault-tolerant mixing supports a single agent mesh axis "
                f"(got {[a for a, _ in factors]}); factored multi-axis "
                "meshes need per-axis fault schedules, not implemented")
        nn = live[0][1].n_agents
        ft = _fault_tables(program)
        # per-agent masked weight rows in the union-stencil layout: slot k
        # at agent i receives from sender (i + shift_k) mod n, so the
        # dense arrival mask folds into a (P, A, 1+U) table exactly the
        # way _self_separated_weights folds the dense Pi
        sched_period = program.schedule.period
        wtab = np.zeros((ft["period"], nn, 1 + len(union_keys)))
        for t in range(ft["period"]):
            e = (t % sched_period) if time_varying else 0
            wm = entry_wire[e]
            for i in range(nn):
                self_w = entry_selfw[e]
                for ki, k in enumerate(union_keys):
                    if k not in wm:
                        continue
                    sender = (i + k[0][1]) % nn
                    if ft["arrive"][t, i, sender]:
                        wtab[t, i, 1 + ki] = wm[k][2]
                    else:
                        self_w += wm[k][2]
                wtab[t, i, 0] = self_w
        w_masked = jnp.asarray(wtab, jnp.float32)
        straggle_t = jnp.asarray(ft["straggle"])
        ages_t = jnp.asarray(ft["ages"], jnp.int32)
        fault_ops = {
            "period": ft["period"], "S": ft["S"],
            "masked_weights":
                lambda t: jnp.take(w_masked, t, axis=0)[_agent_index()],
            "own_straggle":
                lambda t: jnp.take(straggle_t, t, axis=0)[_agent_index()],
            "next_ages":
                lambda t: jnp.take(ages_t, t, axis=0)[_agent_index()][None],
            "init_state":
                lambda: (jnp.zeros((1,), jnp.int32),
                         ages_t[0][_agent_index()][None]),
        }

    strategy = _make_strategy(program, quantize=quantize, exchange_t=exchange_t,
                              combine=combine, wire_to_bufs=wire_to_bufs,
                              legacy_gather=legacy_gather,
                              bufs_to_state=bufs_to_state,
                              state_to_bufs=state_to_bufs,
                              fault_ops=fault_ops,
                              compress=compress, qwarm_init=qwarm_init,
                              meta=meta)

    return FlatComm(lead=lead, batched=False, gather=strategy.gather,
                    interpret=interpret, exchange=exchange, n_agents=n_total,
                    quantize_stage=strategy.quantize_stage,
                    exchange_stage=strategy.exchange_stage,
                    strategy=strategy, program=program)


def _factored_pi(factors) -> np.ndarray:
    pi = np.array([[1.0]])
    for _, t in factors:
        pi = np.kron(pi, t.pi)
    return pi


def widen_with_momentum(fl: FlatComm, bufs, momentum_bufs=None):
    """THE wire-widening convention of ``momentum_mixing="mixed"``, in one
    place: the strategy-facing bucket list is ``params_bufs +
    momentum_bufs`` — equal halves, the momentum half mirroring the param
    buckets one-for-one against the same :class:`FlatSpec`.
    ``momentum_bufs=None`` appends zeros (the initializer convention:
    ``v_{-1} := v_0 = 0`` — the optimizers zero-init their momentum /
    first-moment buffers).  No-op for programs that don't mix momentum.
    """
    if fl.program is None or fl.program.momentum_mixing != "mixed":
        assert momentum_bufs is None, "momentum payload without a mixed program"
        return list(bufs)
    if momentum_bufs is None:
        momentum_bufs = [jnp.zeros_like(b) for b in bufs]
    assert len(momentum_bufs) == len(bufs), (len(momentum_bufs), len(bufs))
    return list(bufs) + list(momentum_bufs)


def initial_wire_state(fl: FlatComm, params: PyTree) -> tuple:
    """Wire state priming the ``schedule="overlap"`` double-buffer.

    The overlap schedule exchanges the *previous* step's quantized buckets;
    before step 0 there is no previous step, so the convention is
    ``x_{-1} := x_0``: quantize the initial params with seed ``-1`` (the
    per-step stages use the optimizer step ``>= 0``, so the stream never
    collides).  Computed on the *global* agent-stacked view — usable
    outside ``shard_map`` — with per-agent seeds identical to what the
    sharded ``axis_index``-seeded quantize stage produces, so both
    execution modes start from the same wire bits.

    For a *sharded* comm this global path assumes the packed layout equals
    the per-device layout — true only when params shard over no non-agent
    mesh axis; the sharded trainer instead initializes per shard with
    :func:`repro.core.engine.make_local_wire_init` inside ``shard_map``.
    """
    if fl.quantize_stage is None:
        raise ValueError("FlatComm has no quantize stage; overlap needs the "
                         "staged flat-buffer comm")
    if fl.lead != 1:
        raise ValueError("overlap wire state assumes one leading agent axis")
    spec = flatbuf.make_flat_spec(params, lead=fl.lead)
    bufs = widen_with_momentum(fl, flatbuf.pack(params, spec))
    seed = jnp.int32(-1)
    if fl.batched:
        # the strategy's initial_wire wraps the seed -1 generation into a
        # WireRing on the fault path (plain quantize_stage otherwise)
        if fl.strategy is not None:
            return fl.strategy.initial_wire(bufs)
        return fl.quantize_stage(bufs, seed)
    # sharded comm, global agent-stacked view: the strategy's quantize is
    # the shard-local one, so replay _quantize_payloads' split on the
    # global quantizer (payload 1 = the momentum half's seed stride)
    if fl.program is not None and fl.program.compressed:
        # compressed wires: replay the stacked compressor with seed -1 and
        # the same per-agent seed composition as the sharded compress;
        # warm-start output discarded (initial_qwarm_state is the basis)
        wire, _ = _compress_wire_stacked(
            bufs, seed, fl.n_agents, fl.program, fl.interpret,
            _qwarm_init_stacked(bufs, fl.n_agents, fl.program))
        return wire
    mixed = fl.program is not None and fl.program.momentum_mixing == "mixed"
    b = len(bufs) // 2 if mixed else len(bufs)
    wire = _quantize_wire_stacked(bufs[:b], seed, fl.n_agents, fl.exchange,
                                  fl.interpret)
    if mixed:
        wire = tuple(wire) + tuple(_quantize_wire_stacked(
            bufs[b:], seed, fl.n_agents, fl.exchange, fl.interpret, payload=1))
    if fl.program is not None and fl.program.fault_tolerant:
        # global view of the per-shard ring init: replicate the seed -1
        # generation across the ring, age counters at their step-0 tables
        ft = _fault_tables(fl.program)
        wire = WireRing(
            slots=tuple((jnp.repeat(p[:, None], ft["S"], axis=1),
                         jnp.repeat(sc[:, None], ft["S"], axis=1))
                        for p, sc in wire),
            send_age=jnp.zeros((fl.n_agents,), jnp.int32),
            ages=jnp.asarray(ft["ages"][0], jnp.int32))
    return wire


def initial_residual_state(fl: FlatComm, params: PyTree) -> tuple:
    """Zero error-feedback residuals for the global agent-stacked view.

    One f32 buffer per flat bucket, shaped like the packed params (leading
    agent axis kept).  The sharded trainer initializes per shard instead
    (:func:`repro.core.engine.make_local_residual_init`) because the local
    flat layout differs whenever params shard over non-agent axes — for
    zeros only the shapes differ, but the shapes are exactly what the
    optimizer-state PartitionSpecs must match.  Both paths build the
    buffers through the same ``MixingStrategy.residual_init``.
    """
    spec = flatbuf.make_flat_spec(params, lead=fl.lead)
    bufs = widen_with_momentum(fl, flatbuf.pack(params, spec))
    return fl.strategy.residual_init(bufs)


def initial_qwarm_state(fl: FlatComm, params: PyTree) -> tuple:
    """Warm-start compressor state for the global agent-stacked view.

    ``()`` unless the program runs the rank-r compressor, in which case
    one ``(A, 128, r)`` orthonormal basis per bucket — the deterministic
    :func:`repro.kernels.consensus_update.topk.rank_init_q` basis,
    identical across agents, buckets and execution modes.  Deliberately
    independent of :func:`initial_wire_state`: the seed ``-1`` priming
    compress discards its warm-start output, so the power-iteration chain
    starts from the init basis in both modes (a quality ramp, not a
    correctness dependency).  The sharded trainer initializes per shard
    via :func:`repro.core.engine.make_local_qwarm_init` instead.
    """
    if fl.program is None or not fl.program.compressed:
        return ()
    spec = flatbuf.make_flat_spec(params, lead=fl.lead)
    bufs = widen_with_momentum(fl, flatbuf.pack(params, spec))
    if fl.batched:
        return fl.strategy.qwarm_init(bufs)
    # sharded comm, global agent-stacked view: replicate the shard-local
    # init basis across the agent axis (it is agent-independent)
    return _qwarm_init_stacked(bufs, fl.n_agents, fl.program)


# --------------------------------------------------------------------------
# Stacked (dense, simulation) path
# --------------------------------------------------------------------------


def mix_stacked(pi: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """``(Pi x)_j = sum_l pi_{jl} x_l`` for ``x`` of shape (N, ...).

    An f32 sum of N broadcast products, not a dot: a dot flattens every
    leaf into one ``(N, size)`` operand, and on a TPU that relayout of a
    large leaf is slower to compile than the rest of the train step.
    """
    pi = jnp.asarray(pi, dtype=jnp.float32)
    xf = x.astype(jnp.float32)
    col = (x.shape[0],) + (1,) * (x.ndim - 1)
    mixed = pi[:, 0].reshape(col) * xf[0]
    for l in range(1, x.shape[0]):
        mixed = mixed + pi[:, l].reshape(col) * xf[l]
    return mixed.astype(x.dtype)


def mix_pytree_stacked(pi: jnp.ndarray, tree: PyTree) -> PyTree:
    """Apply `mix_stacked` to every leaf of an agent-stacked pytree."""
    return jax.tree.map(lambda x: mix_stacked(pi, x), tree)


def mix_pytree_list(pi: np.ndarray, trees: Sequence[PyTree]) -> list:
    """Host-level mixing of a list of per-agent pytrees (tests/benchmarks)."""
    n = len(trees)
    out = []
    for j in range(n):
        out.append(tree_weighted_sum([float(pi[j, l]) for l in range(n)], list(trees)))
    return out


# --------------------------------------------------------------------------
# Sharded (shard_map) path
# --------------------------------------------------------------------------


def _circulant_mix_leaf(x, shifts, axis_name: str, n: int):
    """sum_s w_s * ppermute(x, shift s) — one collective-permute per offset."""
    acc = None
    for s, w in sorted(shifts.items()):
        w = jnp.asarray(w, dtype=x.dtype)
        if s % n == 0:
            term = w * x
        else:
            # agent j receives from agent (j + s) mod n
            perm = [((j + s) % n, j) for j in range(n)]
            term = w * lax.ppermute(x, axis_name, perm=perm)
        acc = term if acc is None else acc + term
    return acc


def _general_mix_leaf(x, pi: jnp.ndarray, axis_name: str):
    """all_gather + row contraction for arbitrary doubly-stochastic Pi."""
    j = lax.axis_index(axis_name)
    gathered = lax.all_gather(x, axis_name)  # (N, ...) local copy
    row = pi[j].astype(jnp.float32)
    flat = gathered.reshape(gathered.shape[0], -1).astype(jnp.float32)
    return (row @ flat).astype(x.dtype).reshape(gathered.shape[1:])


def make_sharded_mix_fn(topology: Topology, axis_name: str) -> MixFn:
    """Mixing function usable *inside* ``shard_map`` over ``axis_name``.

    The returned fn maps a local (per-agent) pytree to its ``Pi``-mixed
    value.  Circulant topologies use ppermute; general ones all_gather.
    """
    n = topology.n_agents
    if n == 1:
        return lambda tree: tree
    shifts = topology.shift_weights()
    if shifts is not None:
        def mix(tree: PyTree) -> PyTree:
            return jax.tree.map(lambda x: _circulant_mix_leaf(x, shifts, axis_name, n), tree)
        return mix
    pi = jnp.asarray(topology.pi, dtype=jnp.float32)

    def mix(tree: PyTree) -> PyTree:
        return jax.tree.map(lambda x: _general_mix_leaf(x, pi, axis_name), tree)

    return mix


def make_sharded_mean_fn(axis_names) -> MixFn:
    """Exact global mean over the agent axes (FedAvg server / centralized)."""

    def mean(tree: PyTree) -> PyTree:
        return jax.tree.map(lambda x: lax.pmean(x, axis_names), tree)

    return mean


@dataclasses.dataclass(frozen=True)
class FactoredMix:
    """Kronecker-factored topology over multiple mesh axes.

    ``factors`` is a sequence of (axis_name, Topology).  The effective
    agent-interaction matrix is ``Pi = Pi_1 (x) Pi_2 (x) ...`` (Kronecker
    product), which is itself doubly stochastic and symmetric PSD when the
    factors are; ``lambda_2(Pi) = max over factors of lambda_2`` (all other
    factor eigenvalues at 1).  Mixing applies each factor sequentially.
    """

    factors: Tuple[Tuple[str, Topology], ...]

    @property
    def n_agents(self) -> int:
        n = 1
        for _, t in self.factors:
            n *= t.n_agents
        return n

    def dense_pi(self) -> np.ndarray:
        pi = np.array([[1.0]])
        for _, t in self.factors:
            pi = np.kron(pi, t.pi)
        return pi

    @property
    def lambda2(self) -> float:
        # kron eigenvalues are products; second-largest = max factor lambda_2
        lams = [t.lambda2 for _, t in self.factors if t.n_agents > 1]
        return max(lams) if lams else 0.0

    @property
    def lambdan(self) -> float:
        prod = 1.0
        for _, t in self.factors:
            prod *= t.lambdan
        return prod

    def make_mix_fn(self) -> MixFn:
        fns = [make_sharded_mix_fn(t, ax) for ax, t in self.factors if t.n_agents > 1]

        def mix(tree: PyTree) -> PyTree:
            for f in fns:
                tree = f(tree)
            return tree

        return mix


# --------------------------------------------------------------------------
# Wire-cost accounting
# --------------------------------------------------------------------------


def program_bytes_per_neighbor(spec: "flatbuf.FlatSpec",
                               program: Optional[MixingProgram],
                               exchange: str = "f32",
                               payloads: int = 1) -> int:
    """Bytes one whole-model transfer moves to ONE neighbor — THE payload
    pricing source (satellite of ISSUE 8).

    Every consumer — :func:`exchange_bytes_per_step`, the trainer/CLI
    printouts, ``engine``'s estimates, and the microbench frontier — prices
    through here, so a new wire contract (e.g. the ragged top-k payload)
    changes the figure everywhere at once instead of silently mispricing
    wherever a dense-payload assumption was duplicated.

    Dense wires (``compressor`` none/int8/fp8) price via
    :meth:`repro.core.flatbuf.FlatSpec.exchange_bytes` at the program's
    wire precision.  Compressed wires price the actual carried fields:

    * ``topk:p`` — per bucket ``k_rows*128`` int8 values + ``k_rows*128``
      int32 indices + ``k_rows`` f32 row scales (ALL of
      :class:`TopKWire` crosses the wire — indices are most of the cost,
      which is why the ≥25x headline needs p≈0.01, not 0.2).
    * ``rank:r`` — per bucket the two dense f32 factors:
      ``(rows*r + r*128) * 4``.

    ``program=None`` falls back to the dense pricing of the ``exchange``/
    ``payloads`` arguments (legacy callers without a program).
    """
    if program is None:
        return int(spec.exchange_bytes(exchange) * payloads)
    kind, param = parse_compressor(program.compressor)
    if kind in ("none", "int8", "fp8"):
        return int(spec.exchange_bytes(program.exchange) * program.n_payloads)
    from repro.kernels.consensus_update import topk as tk

    total = 0
    if kind == "topk":
        k_list = tk.topk_k_rows_for([b.rows for b in spec.buckets], param)
        for k_rows in k_list:
            total += k_rows * tk.TOPK_LANE_ROW_BYTES
    else:
        assert kind == "rank", kind
        r = int(param)
        for b in spec.buckets:
            total += (b.rows * r + r * flatbuf.LANE) * 4
    return total * program.n_payloads


def exchange_bytes_per_step(spec: "flatbuf.FlatSpec", topology,
                            exchange: str = "f32", rounds: int = 1,
                            payloads: int = 1,
                            program: Optional[MixingProgram] = None) -> dict:
    """Per-step bytes-on-wire estimate for the fused consensus exchange.

    The paper's fixed-topology cost model (eq. 5/6): each agent sends/
    receives ``degree`` whole-model transfers per step.  ``per_neighbor``
    comes from :func:`program_bytes_per_neighbor` — dense wires price via
    :meth:`repro.core.flatbuf.FlatSpec.exchange_bytes` for the chosen wire
    precision (int8/fp8 add one f32 scale per 128-lane row); passing
    ``program`` prices compressed wires (top-k / rank-r) from their actual
    carried fields.  ``topology`` may be a
    :class:`repro.core.topology.TopologySchedule` (degree = period
    average), ``rounds`` inner consensus rounds multiply every transfer
    (k-round i-CDSGD moves exactly ``k x`` the single-round bytes; error
    feedback moves zero extra — the residual is local state), and
    ``payloads`` counts the trees on the wire per transfer
    (``momentum_mixing="mixed"`` moves params + momentum = 2).
    """
    per_neighbor = program_bytes_per_neighbor(spec, program, exchange,
                                              payloads)
    if program is not None:
        exchange = (program.compressor if program.compressed
                    else program.exchange)
        payloads = program.n_payloads
    if isinstance(topology, TopologySchedule):
        degree = topology.mean_degree()
    else:
        degree = topology.degree()
    per_step = int(per_neighbor * degree * rounds)
    return {
        "exchange": exchange,
        "degree": degree,
        "rounds": rounds,
        "payloads": payloads,
        "per_neighbor_bytes": per_neighbor,
        "per_step_bytes": per_step,
        "native_per_step_bytes": int(spec.exchange_bytes("f32") * payloads
                                     * degree * rounds),
    }


def mean_exchange_bytes_per_step(spec: "flatbuf.FlatSpec", n_agents: int,
                                 period: int = 1, payloads: int = 1) -> dict:
    """Per-step bytes-on-wire estimate for a *global-mean* optimizer.

    FedAvg's sync step is a brute-force all-reduce of the whole model
    (ring all-reduce: ``2 (N-1)/N`` native-precision model transfers per
    agent), amortized over the ``period = local_steps`` between syncs —
    the collective now being gated on the sync step, an agent pays
    ``bytes / E`` per step instead of the full all-reduce every step.
    ``payloads`` counts the averaged trees (2 when the momentum buffer is
    averaged at sync too, i.e. ``mu != 0``).
    """
    native = spec.exchange_bytes("f32") * payloads
    per_sync = 2.0 * (n_agents - 1) / max(n_agents, 1) * native
    return {
        "exchange": "f32",
        "local_steps": period,
        "payloads": payloads,
        "per_sync_bytes": int(per_sync),
        "per_step_bytes": int(per_sync / max(period, 1)),
    }


def describe_exchange_cost(params: PyTree, topology,
                           exchange: str = "f32", *, lead: int = 1,
                           rounds: int = 1, payloads: int = 1,
                           program: Optional[MixingProgram] = None) -> str:
    """One-line human-readable :func:`exchange_bytes_per_step` report
    (shared by the train/dryrun CLIs and the examples)."""
    spec = flatbuf.make_flat_spec(params, lead=lead)
    wire = exchange_bytes_per_step(spec, topology, exchange, rounds,
                                   payloads, program=program)
    per_round = "" if rounds == 1 else f" x {rounds} rounds"
    per_payload = "" if payloads == 1 else f" ({payloads} payload trees)"
    auto = ""
    if program is not None and program.compressor_kind == "topk" \
            and isinstance(program.compressor_param, tuple):
        # topk:auto:B — surface the per-bucket densities the budget
        # solver actually chose (not the nominal spec string)
        from repro.kernels.consensus_update import topk as tk

        rows_list = [b.rows for b in spec.buckets]
        k_list = tk.topk_k_rows_for(rows_list, program.compressor_param)
        dens = ", ".join(f"{k / r:.3g}" for k, r in zip(k_list, rows_list))
        auto = f"; auto per-bucket p=[{dens}]"
    # the dict relabels compressed wires by their compressor (topk:p/rank:r)
    return (f"exchange={wire['exchange']}: "
            f"{wire['per_step_bytes']:,} bytes/agent/step "
            f"on the wire ({wire['degree']:g} neighbors x "
            f"{wire['per_neighbor_bytes']:,} B{per_round}{per_payload}; native "
            f"{wire['native_per_step_bytes']:,} B){auto}")


# --------------------------------------------------------------------------
# Consensus diagnostics
# --------------------------------------------------------------------------


def consensus_error_stacked(x: jnp.ndarray) -> jnp.ndarray:
    """mean_j ||x_j - mean(x)|| for an agent-stacked leaf (Prop. 1 LHS)."""
    mean = jnp.mean(x, axis=0, keepdims=True)
    diff = (x - mean).reshape(x.shape[0], -1)
    return jnp.mean(jnp.linalg.norm(diff.astype(jnp.float32), axis=1))


def consensus_error_pytree(tree: PyTree) -> jnp.ndarray:
    """Aggregate consensus error over an agent-stacked pytree."""
    leaves = jax.tree.leaves(tree)
    n = leaves[0].shape[0]
    mean_sq = jnp.zeros((n,), dtype=jnp.float32)
    for x in leaves:
        mean = jnp.mean(x, axis=0, keepdims=True)
        d = (x - mean).reshape(n, -1).astype(jnp.float32)
        mean_sq = mean_sq + jnp.sum(d * d, axis=1)
    return jnp.mean(jnp.sqrt(mean_sq))
