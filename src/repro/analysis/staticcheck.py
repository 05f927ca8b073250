"""StepProgram contract checker: static verification of the wire contract.

CDSGD's convergence guarantees hold only if the compiled step actually
implements the configured wire contract — and after the schedule ×
strategy × compressor × staleness × momentum-mixing × faults product
space, that contract is too wide to audit by hand.  This module certifies
any assembled :class:`repro.core.engine.StepProgram` (stacked or sharded)
*before it runs*, by tracing it once and running named passes over the
jaxpr (plus optional HLO evidence), returning a machine-readable
:class:`CheckReport` with pass/fail/evidence per rule.

Pass catalog (rule ids)::

    census.ppermute_count      actual collective-permute eqn count ==
                               closed-form prediction from MixingProgram
    census.critical_path       fresh vs carried-only classification per
                               hit matches the schedule (overlap round 1
                               carries only wire/state labels, 1705.09056)
    census.clean_collectives   no psum/all-gather/… ever touches wire data
    alias.fused_coverage       every fused pallas_call carries the
                               optimizer-declared input_output_aliases
    alias.donation_declared    donate_argnums covers params + opt_state
                               whenever an in-place contract is declared
    alias.double_donation      no buffer is reachable through two donated
                               arguments (the PR 9 Nesterov init bug class)
    alias.dropped_donations    no silently-dropped donations at compile
                               (fed from the HLO buffer-donation report)
    bytes.wire_vs_program      program_bytes_per_neighbor == bytes of the
                               actual carried wire buffers
    bytes.hlo_collective_permute  HLO collective-permute operand bytes ==
                               the accounting prediction (trip-aware)
    seeds.strides_distinct     the five wire_seed strides are distinct
    seeds.window_collision_free  SR seed streams of the configured program
                               are disjoint over a dense + strided window
    seeds.ring_window          …including the depth-S staleness ring window
    sparse.shape_contract      TopKWire/RankWire field shapes + dtypes
    sparse.k_rows_clamp        1 <= k_rows <= rows (and the auto budget)
    sparse.index_bounds        opt-in checkify proof the top-k indices are
                               in range (concrete wire only)

Closed-form collective census (validated on the debug mesh, PR 10)::

    n_ppermute_eqns = sum_entries(non-identity circulant shifts)
                      x fields x n_buckets x n_payloads x callsites
    fields    = 3 (topk: values+indices+scales) | 2 (rank: p+qt)
              | 2 (int8/fp8: payload+scales)    | 1 (f32/bf16)
    callsites = 1 (rounds=1) | 2 (rounds=2) | 3 (rounds>=3; the inner
                rounds live in one lax.scan body, counted once per eqn)
    carried   = total/callsites under schedule="overlap" (round 1 consumes
                the carried wire), 0 under "sync"; stacked mode = 0 total.
    Staleness S never changes the count (one ring slot crosses per shift).

A deliberately-broken program (fresh collective on the claimed-carried
round, a dropped alias, colliding seed strides …) fails the matching
named rule with actionable evidence; tests/test_staticcheck.py asserts
this on hand-assembled breakages.

Adding a pass: write ``pass_<name>(ctx) -> list[RuleResult]`` over the
shared :class:`CheckContext` (one trace, shared by every pass), register
it in ``PASSES``, and document the rule ids above + in ARCHITECTURE.md.
"""

from __future__ import annotations

import dataclasses
import traceback
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import consensus, engine, flatbuf

PyTree = Any

SCHEMA_VERSION = 1


# --------------------------------------------------------------------------
# report types
# --------------------------------------------------------------------------


@dataclasses.dataclass
class RuleResult:
    """One named rule's verdict: pass/fail/skip plus evidence."""

    rule: str
    ok: bool
    detail: str = ""
    evidence: Dict[str, Any] = dataclasses.field(default_factory=dict)
    skipped: bool = False          # not applicable / not provable here

    def as_dict(self) -> dict:
        return {"rule": self.rule, "ok": bool(self.ok),
                "skipped": bool(self.skipped), "detail": self.detail,
                "evidence": _jsonable(self.evidence)}


@dataclasses.dataclass
class CheckReport:
    """Machine-readable verdict of every pass over one program config."""

    label: str
    mode: str                      # "stacked" | "sharded"
    schedule: str
    results: List[RuleResult] = dataclasses.field(default_factory=list)
    walltime_s: float = 0.0

    @property
    def ok(self) -> bool:
        return all(r.ok for r in self.results)

    def failures(self) -> List[RuleResult]:
        return [r for r in self.results if not r.ok]

    def rule(self, rule_id: str) -> RuleResult:
        for r in self.results:
            if r.rule == rule_id:
                return r
        raise KeyError(rule_id)

    def as_dict(self) -> dict:
        return {"version": SCHEMA_VERSION, "label": self.label,
                "mode": self.mode, "schedule": self.schedule,
                "ok": self.ok, "walltime_s": round(self.walltime_s, 3),
                "rules": [r.as_dict() for r in self.results]}

    def summary(self) -> str:
        lines = [f"[{'OK' if self.ok else 'FAIL'}] {self.label} "
                 f"({self.mode}/{self.schedule})"]
        for r in self.results:
            mark = "skip" if r.skipped else ("ok" if r.ok else "FAIL")
            line = f"  {mark:>4}  {r.rule}"
            if r.detail and (not r.ok or r.skipped):
                line += f" — {r.detail}"
            lines.append(line)
        return "\n".join(lines)


def _jsonable(x):
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple, set, frozenset)):
        return [_jsonable(v) for v in x]
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.floating,)):
        return float(x)
    if isinstance(x, (str, int, float, bool)) or x is None:
        return x
    return str(x)


# --------------------------------------------------------------------------
# context: one trace shared by every pass
# --------------------------------------------------------------------------


@dataclasses.dataclass
class CheckContext:
    """Everything the passes consume, assembled once per configuration."""

    label: str
    mode: str                           # "stacked" | "sharded"
    schedule: str                       # "sync" | "overlap"
    program: Optional[consensus.MixingProgram]
    optimizer: Any
    spec: flatbuf.FlatSpec              # global (agent-stacked) flat layout
    n_agents: int
    step_fn: Any
    params: PyTree                      # concrete arrays or SDS structs
    opt_state: Any
    batch: Any
    donate_argnums: Tuple[int, ...] = ()
    hlo_stats: Any = None               # repro.analysis.hlo.HloStats | None
    row_shard: int = 1                  # model-axis shards of each bucket row
    dropped_donations: Optional[List[str]] = None
    checkify_indices: bool = False
    # filled by assemble():
    closed_jaxpr: Any = None
    hits: Optional[List[dict]] = None   # collective taint hits
    wire_carried: Any = None            # the actual carried wire entries
    wire_global: Any = None             # global-layout template (eval_shape)

    def assemble(self) -> "CheckContext":
        self.closed_jaxpr = jax.make_jaxpr(self.step_fn)(
            self.params, self.opt_state, self.batch)
        self.hits = engine.collective_taint_hits(
            self.step_fn, self.params, self.opt_state, self.batch,
            prims=engine.COLLECTIVE_PRIMS, closed=self.closed_jaxpr)
        wire = getattr(self.opt_state, "wire", ())
        if isinstance(wire, consensus.WireRing) or (
                isinstance(wire, (tuple, list)) and len(wire)):
            self.wire_carried = wire
        self.wire_global = self._synthesize_global_wire()
        return self

    @property
    def wire_template(self):
        """Best wire-contract template available: the carried entries when
        they follow the global layout, else the synthesized one (sync
        schedules carry none; model-sharded buckets re-pad per shard)."""
        if self.wire_carried is not None and self.row_shard == 1:
            return self.wire_carried
        return self.wire_global or self.wire_carried

    def _synthesize_global_wire(self):
        """The wire contract of the *global* flat layout, synthesized
        shape-only via ``jax.eval_shape`` of the stacked wire initializer
        — no kernel runs, works on concrete arrays or structs."""
        if self.program is None:
            return None
        try:
            topo = self.program.schedule.topologies[0]
            fl = consensus.stacked_flat_comm(
                topo, exchange=self.program.exchange, program=self.program)
            return jax.eval_shape(
                lambda p: consensus.initial_wire_state(fl, p), self.params)
        except Exception:
            return None


# --------------------------------------------------------------------------
# closed-form collective prediction
# --------------------------------------------------------------------------


def predict_collectives(program: Optional[consensus.MixingProgram],
                        spec: flatbuf.FlatSpec, schedule: str,
                        mode: str) -> dict:
    """Closed-form ppermute census of a program configuration.

    Returns ``{total, carried, fresh, breakdown}`` where breakdown names
    every factor; ``total`` is None when the config is outside the model
    (non-circulant sharded topology, factored multi-axis mesh)."""
    if mode == "stacked":
        return {"total": 0, "carried": 0, "fresh": 0,
                "breakdown": {"mode": "stacked — dense Pi matmul, "
                                      "no collectives"}}
    if program is None:
        return {"total": None, "carried": None, "fresh": None,
                "breakdown": {"reason": "no MixingProgram (dense/ppermute "
                                        "legacy mixing)"}}
    entry_shifts = []
    for topo in program.schedule.topologies:
        sw = topo.shift_weights()
        if sw is None:
            return {"total": None, "carried": None, "fresh": None,
                    "breakdown": {"reason": f"topology {topo.name!r} is "
                                            "not circulant"}}
        n = topo.n_agents
        entry_shifts.append(len([s for s in sw if s % n != 0]))
    kind = program.compressor_kind
    if kind == "topk":
        fields = 3                     # values + indices + scales
    elif kind == "rank":
        fields = 2                     # p + qt factors
    elif program.exchange in ("int8", "fp8"):
        fields = 2                     # payload + row scales
    else:
        fields = 1                     # f32/bf16 payload only
    rounds = program.rounds
    callsites = 1 if rounds == 1 else (2 if rounds == 2 else 3)
    per_site = sum(entry_shifts) * fields * spec.n_buckets \
        * program.n_payloads
    total = per_site * callsites
    carried = per_site if schedule == "overlap" else 0
    return {
        "total": total, "carried": carried, "fresh": total - carried,
        "breakdown": {
            "entry_shifts": entry_shifts, "fields": fields,
            "n_buckets": spec.n_buckets, "n_payloads": program.n_payloads,
            "rounds": rounds, "callsites": callsites,
            "staleness": program.staleness,
        },
    }


# --------------------------------------------------------------------------
# pass 1: collective census
# --------------------------------------------------------------------------


def pass_collective_census(ctx: CheckContext) -> List[RuleResult]:
    pred = predict_collectives(ctx.program, ctx.spec, ctx.schedule, ctx.mode)
    pp = [h for h in ctx.hits if "ppermute" in h["prim"]]
    carried = [h for h in pp
               if not (h["labels"] & frozenset(("params", "batch")))]
    fresh = [h for h in pp if h["labels"] & frozenset(("params", "batch"))]
    ev = {
        "actual": len(pp), "actual_carried": len(carried),
        "actual_fresh": len(fresh), "predicted": pred["total"],
        "predicted_carried": pred["carried"],
        "predicted_fresh": pred["fresh"], "breakdown": pred["breakdown"],
    }
    out = []
    if pred["total"] is None:
        out.append(RuleResult(
            "census.ppermute_count", ok=True, skipped=True,
            detail=f"no closed-form prediction: "
                   f"{pred['breakdown'].get('reason')}", evidence=ev))
        out.append(RuleResult("census.critical_path", ok=True, skipped=True,
                              detail="prediction unavailable", evidence=ev))
    else:
        out.append(RuleResult(
            "census.ppermute_count", ok=len(pp) == pred["total"],
            detail=(f"{len(pp)} collective-permute eqns, predicted "
                    f"{pred['total']} = sum(shifts)"
                    f"{pred['breakdown'].get('entry_shifts', '')} x "
                    f"{pred['breakdown'].get('fields')} fields x "
                    f"{pred['breakdown'].get('n_buckets')} buckets x "
                    f"{pred['breakdown'].get('n_payloads')} payloads x "
                    f"{pred['breakdown'].get('callsites')} callsites"),
            evidence=ev))
        cls_ev = dict(ev)
        cls_ev["fresh_hits"] = [
            {"prim": h["prim"], "labels": sorted(h["labels"])}
            for h in fresh]
        ok = (len(carried) == pred["carried"]
              and len(fresh) == pred["fresh"])
        detail = (f"{len(carried)} carried-only / {len(fresh)} fresh; "
                  f"predicted {pred['carried']}/{pred['fresh']} under "
                  f"schedule={ctx.schedule!r}")
        if not ok and ctx.schedule == "overlap" \
                and len(carried) < (pred["carried"] or 0):
            detail += (" — a collective the overlap contract requires to "
                       "consume only carried wire state reads fresh "
                       "params/batch: the exchange is back on the "
                       "grad->update critical path")
        out.append(RuleResult("census.critical_path", ok=ok, detail=detail,
                              evidence=cls_ev))
    others = [h for h in ctx.hits if "ppermute" not in h["prim"]]
    bad = [h for h in others if "wire" in h["labels"]
           or "params" in h["labels"]]
    out.append(RuleResult(
        "census.clean_collectives", ok=not bad,
        detail=("no non-ppermute collective touches wire/param data"
                if not bad else
                f"{len(bad)} unintended collective(s) carry wire/param "
                f"data: {[h['prim'] for h in bad]}"),
        evidence={"non_ppermute_collectives": [
            {"prim": h["prim"], "labels": sorted(h["labels"])}
            for h in others]}))
    return out


# --------------------------------------------------------------------------
# pass 2: alias / donation coverage
# --------------------------------------------------------------------------


def pass_alias_donation(ctx: CheckContext) -> List[RuleResult]:
    from repro.kernels.consensus_update import ops as kops

    out = []
    expected = getattr(ctx.optimizer, "fused_alias_pairs", None)
    fused = bool(getattr(ctx.optimizer, "fused", False))
    if expected is None or not fused:
        out.append(RuleResult(
            "alias.fused_coverage", ok=True, skipped=True,
            detail="optimizer declares no fused in-place contract"))
    else:
        groups = kops.alias_groups(ctx.closed_jaxpr)
        aliased = [g for g in groups if g]
        bad_len = [g for g in aliased if len(g) != expected]
        ok = len(aliased) == ctx.spec.n_buckets and not bad_len
        detail = (f"{len(aliased)}/{ctx.spec.n_buckets} fused launches "
                  f"alias in place, {expected} pair(s) each declared by "
                  f"{type(ctx.optimizer).__name__}")
        if len(aliased) < ctx.spec.n_buckets:
            detail += (" — a fused bucket launch dropped its "
                       "input_output_aliases: the update silently copies "
                       "instead of updating in place")
        elif bad_len:
            detail += (f" — launches with wrong pair counts: "
                       f"{[len(g) for g in bad_len]}")
        out.append(RuleResult(
            "alias.fused_coverage", ok=ok, detail=detail,
            evidence={"groups": groups, "expected_pairs": expected,
                      "n_buckets": ctx.spec.n_buckets}))
        cov = set(ctx.donate_argnums) >= {0, 1}
        out.append(RuleResult(
            "alias.donation_declared", ok=cov,
            detail=("params + opt_state donated to the jitted step"
                    if cov else
                    f"donate_argnums={ctx.donate_argnums} does not cover "
                    "(params, opt_state): the declared in-place aliases "
                    "cannot elide the output copies"),
            evidence={"donate_argnums": list(ctx.donate_argnums)}))

    out.append(_double_donation_rule(ctx))

    if ctx.dropped_donations is None:
        out.append(RuleResult(
            "alias.dropped_donations", ok=True, skipped=True,
            detail="no compile-time donation report supplied"))
    else:
        real = [w for w in ctx.dropped_donations
                if "not implemented for" not in w]
        platform_only = [w for w in ctx.dropped_donations
                         if "not implemented for" in w]
        out.append(RuleResult(
            "alias.dropped_donations", ok=not real,
            detail=("no silently-dropped donations" if not real else
                    f"{len(real)} donation(s) dropped at compile"),
            evidence={"dropped": real,
                      "platform_unsupported": platform_only}))
    return out


def _double_donation_rule(ctx: CheckContext) -> RuleResult:
    """The PR 9 Nesterov bug class: the same buffer reachable through two
    donated jit arguments is donated twice — a runtime error on the first
    step, invisible to shape-level checks."""
    if not set(ctx.donate_argnums) >= {0, 1}:
        return RuleResult("alias.double_donation", ok=True, skipped=True,
                          detail="params/opt_state not both donated")
    donated = {0: ctx.params, 1: ctx.opt_state}
    leaves: Dict[int, List[str]] = {}
    concrete = True
    for argi, tree in donated.items():
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        for path, leaf in flat:
            if not isinstance(leaf, jax.Array):
                concrete = False
                continue
            key = id(leaf)
            name = f"arg{argi}{jax.tree_util.keystr(path)}"
            leaves.setdefault(key, []).append(name)
    if not concrete and not leaves:
        return RuleResult("alias.double_donation", ok=True, skipped=True,
                          detail="abstract (ShapeDtypeStruct) inputs — "
                                 "buffer identity not checkable")
    dups = {names[0]: names for names in leaves.values() if len(names) > 1}
    return RuleResult(
        "alias.double_donation", ok=not dups,
        detail=("no buffer is donated twice" if not dups else
                f"{len(dups)} buffer(s) reachable through multiple donated "
                "leaves — donating the same buffer twice is a runtime "
                "error on the first step (copy at init instead, like "
                "CDMSGDNesterov.init_inner's lookahead)"),
        evidence={"duplicates": list(dups.values())})


def compile_donation_report(step_fn, donate_argnums, *args) -> List[str]:
    """Compile ``step_fn`` capturing jax's dropped-donation warnings; feed
    the result to :class:`CheckContext` as ``dropped_donations``."""
    import warnings as _warnings

    with _warnings.catch_warnings(record=True) as caught:
        _warnings.simplefilter("always")
        jax.jit(step_fn, donate_argnums=donate_argnums).lower(
            *args).compile()
    return [str(w.message) for w in caught
            if "donat" in str(w.message).lower()]


# --------------------------------------------------------------------------
# pass 3: byte-accounting cross-check
# --------------------------------------------------------------------------


def pass_byte_accounting(ctx: CheckContext) -> List[RuleResult]:
    out = []
    if ctx.program is None:
        return [RuleResult("bytes.wire_vs_program", ok=True, skipped=True,
                           detail="no MixingProgram to price")]
    per_nbr = consensus.program_bytes_per_neighbor(
        ctx.spec, ctx.program, ctx.program.exchange, ctx.program.n_payloads)
    if ctx.wire_template is None:
        out.append(RuleResult(
            "bytes.wire_vs_program", ok=True, skipped=True,
            detail="no carried wire and the template synthesis failed",
            evidence={"program_bytes_per_neighbor": per_nbr}))
    else:
        actual = engine.wire_bytes_per_neighbor(ctx.wire_template)
        ev = {"wire_bytes_per_neighbor": actual,
              "program_bytes_per_neighbor": per_nbr}
        detail = (f"wire contract moves {actual} B/neighbor, accounting "
                  f"prices {per_nbr} B")
        if ctx.wire_carried is not None and ctx.row_shard != 1:
            # model-sharded buckets re-pad per shard, so the carried
            # struct's global shape over-counts padding; the rule compares
            # the global-layout template and records the carried figure
            carried = engine.wire_bytes_per_neighbor(ctx.wire_carried)
            ev["carried_bytes_per_neighbor"] = carried
            ev["per_shard_padding_bytes"] = carried - actual
            detail += (f" (carried per-shard layout: {carried} B, "
                       f"+{carried - actual} B repadding over "
                       f"{ctx.row_shard} row shards)")
        out.append(RuleResult(
            "bytes.wire_vs_program", ok=actual == per_nbr, detail=detail,
            evidence=ev))

    if ctx.hlo_stats is None:
        out.append(RuleResult(
            "bytes.hlo_collective_permute", ok=True, skipped=True,
            detail="no HLO stats supplied"))
        return out
    cp_bytes = int(ctx.hlo_stats.collective_bytes.get(
        "collective-permute", 0))
    pred = predict_collectives(ctx.program, ctx.spec, ctx.schedule, ctx.mode)
    shifts = pred["breakdown"].get("entry_shifts")
    if ctx.mode == "stacked":
        out.append(RuleResult(
            "bytes.hlo_collective_permute", ok=cp_bytes == 0,
            detail=f"stacked mode must ship 0 collective-permute bytes, "
                   f"HLO shows {cp_bytes}",
            evidence={"hlo_cp_bytes": cp_bytes}))
        return out
    if shifts is None or ctx.row_shard != 1:
        out.append(RuleResult(
            "bytes.hlo_collective_permute", ok=True, skipped=True,
            detail=(f"model-sharded buckets (row_shard={ctx.row_shard}) "
                    "re-pad per shard; per-device equality not provable "
                    "from the global spec" if ctx.row_shard != 1 else
                    "no closed-form shift count"),
            evidence={"hlo_cp_bytes": cp_bytes,
                      "program_bytes_per_neighbor": per_nbr}))
        return out
    # trip-aware HLO totals: every switch branch counts once, the
    # multi-round scan body counts trip times -> rounds multiplier
    expect = per_nbr * sum(shifts) * ctx.program.rounds
    out.append(RuleResult(
        "bytes.hlo_collective_permute", ok=cp_bytes == expect,
        detail=(f"HLO moves {cp_bytes} B through collective-permute; "
                f"accounting predicts {expect} = {per_nbr} B/neighbor x "
                f"sum(shifts){shifts} x {ctx.program.rounds} round(s)"),
        evidence={"hlo_cp_bytes": cp_bytes, "expected": expect,
                  "per_neighbor": per_nbr, "entry_shifts": shifts,
                  "rounds": ctx.program.rounds,
                  "hlo_cp_count": int(ctx.hlo_stats.collective_count.get(
                      "collective-permute", 0))}))
    return out


# --------------------------------------------------------------------------
# pass 4: seed-stream lint
# --------------------------------------------------------------------------


def _seed_grid(steps: np.ndarray, rounds: int, agents: int, buckets: int,
               payloads: int) -> np.ndarray:
    """Vectorized wire_seed over the full index grid, wrapped to uint32.
    ``steps`` may be any broadcastable integer array (dense windows, the
    staleness ring's ``t - s`` plane, strided probes)."""
    st = np.int64(consensus._SEED_STEP_STRIDE)
    ag = np.int64(consensus._SEED_AGENT_STRIDE)
    bu = np.int64(consensus._SEED_BUCKET_STRIDE)
    ro = np.int64(consensus._SEED_ROUND_STRIDE)
    pa = np.int64(consensus._SEED_PAYLOAD_STRIDE)
    s = (st * (steps[..., None, None, None, None]
               + ro * np.arange(rounds)[:, None, None, None])
         + ag * np.arange(agents)[:, None, None]
         + bu * np.arange(buckets)[:, None]
         + pa * np.arange(payloads))
    return (s & 0xFFFFFFFF).ravel()


def pass_seed_streams(ctx: CheckContext) -> List[RuleResult]:
    prog = ctx.program
    quantized = prog is not None and (
        prog.exchange in ("int8", "fp8") or prog.compressor_kind == "topk")
    strides = {
        "step": consensus._SEED_STEP_STRIDE,
        "agent": consensus._SEED_AGENT_STRIDE,
        "bucket": consensus._SEED_BUCKET_STRIDE,
        "round": consensus._SEED_ROUND_STRIDE,
        "payload": consensus._SEED_PAYLOAD_STRIDE,
    }
    out = [RuleResult(
        "seeds.strides_distinct",
        ok=len(set(strides.values())) == len(strides)
        and all(v != 0 for v in strides.values()),
        detail="the five wire_seed strides are distinct and nonzero",
        evidence={"strides": strides})]
    if not quantized:
        out.append(RuleResult(
            "seeds.window_collision_free", ok=True, skipped=True,
            detail="no stochastic rounding on this wire "
                   f"(exchange={getattr(prog, 'exchange', 'f32')!r})"))
        return out

    rounds, agents = prog.rounds, ctx.n_agents
    buckets, payloads = ctx.spec.n_buckets, prog.n_payloads

    def _distinct(steps):
        seeds = _seed_grid(np.asarray(steps, np.int64), rounds, agents,
                           buckets, payloads)
        return len(np.unique(seeds)) == seeds.size, seeds.size

    dense_ok, dense_n = _distinct(np.arange(128))
    probe_ok, probe_n = _distinct((np.arange(997) * 1003 + 13) % 1_000_000)
    # spot-check the vectorized grid against the canonical wire_seed
    rng = np.random.default_rng(0)
    spot_ok = True
    for _ in range(8):
        t = int(rng.integers(0, 1_000_000))
        a = int(rng.integers(0, agents))
        b = int(rng.integers(0, buckets))
        r = int(rng.integers(0, rounds))
        p = int(rng.integers(0, payloads))
        want = consensus.wire_seed(t, a, b, r, p) & 0xFFFFFFFF
        got = int(_seed_grid(np.asarray([t], np.int64), r + 1, a + 1,
                             b + 1, p + 1)[-1])
        spot_ok = spot_ok and got == want
    out.append(RuleResult(
        "seeds.window_collision_free",
        ok=dense_ok and probe_ok and spot_ok,
        detail=(f"SR streams disjoint over a dense 128-step window "
                f"({dense_n} seeds) and a 997-step strided probe "
                f"({probe_n} seeds) at {agents} agents x {buckets} "
                f"buckets x {rounds} round(s) x {payloads} payload(s)"
                + ("" if spot_ok else
                   " — grid disagrees with wire_seed()")),
        evidence={"dense_window_ok": dense_ok, "probe_ok": probe_ok,
                  "matches_wire_seed": spot_ok,
                  "dense_seeds": dense_n, "probe_seeds": probe_n}))

    if prog.staleness > 1:
        S = prog.staleness
        base = np.arange(64) + S
        window = base[:, None] - np.arange(S + 1)     # (steps, S+1)
        ring_seeds = _seed_grid(window.astype(np.int64), rounds, agents,
                                buckets, payloads)
        # the same (t - s) plane repeats across consecutive steps; dedupe
        # per distinct step value, then require global uniqueness
        uniq_steps = np.unique(window)
        flat = _seed_grid(uniq_steps.astype(np.int64), rounds, agents,
                          buckets, payloads)
        ok = len(np.unique(flat)) == flat.size
        out.append(RuleResult(
            "seeds.ring_window", ok=ok,
            detail=f"depth-{S} staleness ring window seeds are disjoint "
                   f"({flat.size} seeds over {len(uniq_steps)} steps)",
            evidence={"staleness": S, "n_seeds": int(flat.size),
                      "n_window_seeds": int(ring_seeds.size)}))
    return out


# --------------------------------------------------------------------------
# pass 5: sparse-wire invariants
# --------------------------------------------------------------------------


def pass_sparse_wire(ctx: CheckContext) -> List[RuleResult]:
    prog = ctx.program
    if prog is None or not prog.compressed:
        return [RuleResult("sparse.shape_contract", ok=True, skipped=True,
                           detail="dense wire (no compressor)")]
    from repro.kernels.consensus_update import topk as tk

    kind, param = consensus.parse_compressor(prog.compressor)
    rows = [b.rows for b in ctx.spec.buckets]
    entries = _wire_entries(ctx.wire_template)
    out = []
    if entries is None:
        out.append(RuleResult(
            "sparse.shape_contract", ok=True, skipped=True,
            detail="no wire template to validate",
            evidence={"compressor": prog.compressor}))
        return out

    problems: List[str] = []
    if kind == "topk":
        k_list = tk.topk_k_rows_for(rows, param)
        for bi, (e, k, r) in enumerate(zip(entries, k_list, rows)):
            if not isinstance(e, consensus.TopKWire):
                problems.append(f"bucket {bi}: expected TopKWire, got "
                                f"{type(e).__name__}")
                continue
            for fname, f, shp, dt in (
                    ("values", e.values, (k, flatbuf.LANE), jnp.int8),
                    ("indices", e.indices, (k, flatbuf.LANE), jnp.int32),
                    ("scales", e.scales, (k, 1), jnp.float32)):
                if tuple(f.shape[-2:]) != shp or f.dtype != dt:
                    problems.append(
                        f"bucket {bi} {fname}: {f.shape}/{f.dtype} != "
                        f"(*, {shp[0]}, {shp[1]})/{jnp.dtype(dt).name}")
        clamp_ok = all(1 <= k <= r for k, r in zip(k_list, rows))
        clamp_detail = (f"k_rows {k_list} clamped into [1, rows] "
                        f"{rows}")
        budget_ev = {}
        if isinstance(param, tuple):          # ("auto", budget_bytes)
            budget = int(param[1])
            spend = sum(k * tk.TOPK_LANE_ROW_BYTES for k in k_list)
            over = spend > budget and any(k > 1 for k in k_list)
            clamp_ok = clamp_ok and not over
            budget_ev = {"budget_bytes": budget, "spend_bytes": spend}
            clamp_detail += f"; auto budget {budget} B, spend {spend} B"
        out.append(RuleResult(
            "sparse.k_rows_clamp", ok=clamp_ok, detail=clamp_detail,
            evidence={"k_rows": list(k_list), "rows": rows, **budget_ev}))
    else:
        assert kind == "rank", kind
        r = int(param)
        for bi, (e, rw) in enumerate(zip(entries, rows)):
            if not isinstance(e, consensus.RankWire):
                problems.append(f"bucket {bi}: expected RankWire, got "
                                f"{type(e).__name__}")
                continue
            for fname, f, shp in (("p", e.p, (rw, r)),
                                  ("qt", e.qt, (r, flatbuf.LANE))):
                if tuple(f.shape[-2:]) != shp or f.dtype != jnp.float32:
                    problems.append(
                        f"bucket {bi} {fname}: {f.shape}/{f.dtype} != "
                        f"(*, {shp[0]}, {shp[1]})/float32")
        out.append(RuleResult(
            "sparse.k_rows_clamp", ok=1 <= r,
            detail=f"rank r={r} >= 1", evidence={"rank": r, "rows": rows}))
    out.insert(0, RuleResult(
        "sparse.shape_contract", ok=not problems,
        detail=("every compressed wire field matches the static "
                f"{kind} contract" if not problems else
                "; ".join(problems)),
        evidence={"compressor": prog.compressor,
                  "n_entries": len(entries), "problems": problems}))

    out.append(_index_bounds_rule(ctx, kind, rows, entries))
    return out


def _wire_entries(wire):
    if wire is None:
        return None
    if isinstance(wire, consensus.WireRing):
        return list(wire.slots)
    if isinstance(wire, (tuple, list)) and len(wire):
        return list(wire)
    return None


def _index_bounds_rule(ctx, kind, rows, entries) -> RuleResult:
    if kind != "topk":
        return RuleResult("sparse.index_bounds", ok=True, skipped=True,
                          detail="rank wire carries no indices")
    if not ctx.checkify_indices:
        return RuleResult("sparse.index_bounds", ok=True, skipped=True,
                          detail="opt-in: pass checkify_indices=True")
    if any(not isinstance(f, jax.Array)
           for e in entries for f in (e.indices,)):
        return RuleResult("sparse.index_bounds", ok=True, skipped=True,
                          detail="abstract wire — checkify needs concrete "
                                 "indices")
    from jax.experimental import checkify

    msgs = []
    for bi, (e, r) in enumerate(zip(entries, rows)):
        dense = r * flatbuf.LANE

        def gather(idx, dense=dense):
            return jnp.zeros((dense,), jnp.float32)[idx.reshape(-1)]

        err, _ = checkify.checkify(
            gather, errors=checkify.index_checks)(e.indices)
        m = err.get()
        if m:
            msgs.append(f"bucket {bi}: {m}")
    return RuleResult(
        "sparse.index_bounds", ok=not msgs,
        detail=("checkify proves every top-k index in range"
                if not msgs else "; ".join(msgs)),
        evidence={"buckets_checked": len(entries), "errors": msgs})


# --------------------------------------------------------------------------
# orchestration
# --------------------------------------------------------------------------


PASSES = {
    "census": pass_collective_census,
    "alias": pass_alias_donation,
    "bytes": pass_byte_accounting,
    "seeds": pass_seed_streams,
    "sparse": pass_sparse_wire,
}


def run_passes(ctx: CheckContext,
               passes: Optional[Sequence[str]] = None) -> CheckReport:
    """Assemble the shared trace and run every (or the named) pass."""
    import time

    t0 = time.perf_counter()
    ctx.assemble()
    results: List[RuleResult] = []
    for name in (passes or PASSES):
        try:
            results.extend(PASSES[name](ctx))
        except Exception:
            results.append(RuleResult(
                f"{name}.error", ok=False,
                detail="pass crashed (checker bug or unsupported program "
                       "shape)",
                evidence={"traceback": traceback.format_exc(limit=8)}))
    return CheckReport(label=ctx.label, mode=ctx.mode, schedule=ctx.schedule,
                       results=results,
                       walltime_s=time.perf_counter() - t0)


def check_program(step_fn, params, opt_state, batch, *, program, optimizer,
                  schedule: str, mode: str, n_agents: int, spec=None,
                  label: str = "", donate_argnums: Tuple[int, ...] = (0, 1),
                  hlo_stats=None, row_shard: int = 1,
                  dropped_donations=None, checkify_indices: bool = False,
                  passes: Optional[Sequence[str]] = None) -> CheckReport:
    """Certify one assembled step function (the low-level entry point).

    ``params``/``opt_state``/``batch`` may be concrete arrays or
    ``ShapeDtypeStruct`` templates — the checker only traces.  ``spec``
    defaults to the global flat layout of ``params``.
    """
    if spec is None:
        spec = flatbuf.make_flat_spec(params, lead=1)
    ctx = CheckContext(
        label=label or f"{mode}/{schedule}", mode=mode, schedule=schedule,
        program=program, optimizer=optimizer, spec=spec, n_agents=n_agents,
        step_fn=step_fn, params=params, opt_state=opt_state, batch=batch,
        donate_argnums=tuple(donate_argnums), hlo_stats=hlo_stats,
        row_shard=row_shard, dropped_donations=dropped_donations,
        checkify_indices=checkify_indices)
    return run_passes(ctx, passes)


def check_trainer(trainer, batch, *, label: str = "", hlo_stats=None,
                  dropped_donations=None, checkify_indices: bool = False,
                  passes: Optional[Sequence[str]] = None) -> CheckReport:
    """Certify a stacked :class:`repro.core.trainer.CollaborativeTrainer`."""
    return check_program(
        trainer._program.step_fn, trainer.state.params,
        trainer.state.opt_state, batch,
        program=trainer.program, optimizer=trainer.optimizer,
        schedule=trainer.schedule, mode="stacked",
        n_agents=trainer.topology.n_agents,
        label=label or f"stacked/{trainer.schedule}",
        donate_argnums=getattr(trainer, "donate_argnums", (0, 1)),
        hlo_stats=hlo_stats, dropped_donations=dropped_donations,
        checkify_indices=checkify_indices, passes=passes)


def check_bundle(bundle, mesh, batch=None, *, label: str = "",
                 hlo_stats=None, row_shard: Optional[int] = None,
                 dropped_donations=None,
                 passes: Optional[Sequence[str]] = None) -> CheckReport:
    """Certify a sharded :class:`repro.launch.steps.TrainStepBundle` from
    its shape templates (no data, no compile)."""
    params = bundle.param_structs(mesh)
    opt_state = bundle.opt_state_structs(mesh, bundle.optimizer)
    if batch is None:
        batch = bundle.batch_specs
    if row_shard is None:
        # "data"/"pod" carry agents (rows stay whole); every other axis
        # ("model", …) shards the bucket rows and re-pads per shard
        agent_axes = {"replica", "agent", "data", "pod"}
        row_shard = 1
        for name, size in dict(mesh.shape).items():
            if name not in agent_axes:
                row_shard *= int(size)
    return check_program(
        bundle.step_fn, params, opt_state, batch,
        program=bundle.mixing_program, optimizer=bundle.optimizer,
        schedule=bundle.schedule, mode="sharded",
        n_agents=bundle.n_agents, label=label or f"sharded/{bundle.schedule}",
        donate_argnums=bundle.donate_argnums, hlo_stats=hlo_stats,
        row_shard=row_shard, dropped_donations=dropped_donations,
        passes=passes)
