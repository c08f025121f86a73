"""Model -> adder-graph compiler: the hls4ml+da4ml integration analogue.

``compile_model`` walks a quantized ``Sequential``, replaces every CMVM
(QDense / QDenseOnAxis / QConv2D-via-im2col) by a da4ml-optimized DAIS
program (strategy="da") or by the per-output naive CSD tree
(strategy="latency", the hls4ml latency-strategy baseline), and stitches
the layers into a bit-exact *integer* executor plus a resource report
(adders, cost bits ~ LUTs, FF estimate from pipelining, adder depth,
latency in pipeline stages) mirroring the paper's network tables.

Exact quantized intervals are propagated feature-by-feature through the
whole network — ReLU clips, pool merges, residual sums — so downstream
CMVMs are solved with true per-input ranges (tighter adders than blanket
bitwidths; this is the qint machinery of paper §4.1 applied end-to-end).

Internal convention: activations flow as int32 [batch, prod(shape)] in
C-order, with ``shape`` (batch excluded) and per-feature ``QInterval``
tracked symbolically at compile time.
"""

from __future__ import annotations

import concurrent.futures
import os
import time
from dataclasses import dataclass, field
from collections.abc import Callable
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.cache import SolutionCache, solve_key
from ..core.fixed_point import QInterval
from ..core.pipelining import pipeline
from ..core.solver import (
    Solution,
    config_solve_key,
    solve_task,
)
from ..flow.config import UNSET, CompileConfig, SolverConfig, resolve_legacy
from ..kernels.adder_graph import adder_graph_apply, compile_tables
from ..kernels.adder_graph.dot import cmvm_conv, cmvm_dot, dot_matrix
from ..obs import trace
from .layers import (
    AvgPool2D,
    Flatten,
    MaxPool2D,
    QConv2D,
    QDense,
    QDenseOnAxis,
    ReLU,
    Residual,
    Sequential,
)
from .quant import QuantConfig


@dataclass
class LayerReport:
    name: str
    shape: str
    adders: int
    cost_bits: int
    depth: int
    stages: int
    ff_bits: int
    solver_time_s: float


@dataclass
class StepSpec:
    """Declarative description of one executor step.

    The compiled design's execution pipeline is a list of these specs;
    :func:`build_steps` turns them into jnp callables.  Because the
    artifact loader (repro.runtime.artifact) rebuilds steps through the
    same builder, a design restored from disk executes byte-for-byte the
    same program as the design that was saved.

    kind    one of dense / conv / requant / transpose / relu / maxpool /
            avgpool / residual.
    params  JSON-serializable scalars (shapes, strides, clip bounds).
    arrays  integer numpy arrays (bias, pre-shift, requant shifts).
    table   index into ``CompiledDesign.tables`` for CMVM kinds, else -1.
    body    nested specs (residual only).
    """

    kind: str
    params: dict = field(default_factory=dict)
    arrays: dict = field(default_factory=dict)
    table: int = -1
    body: list["StepSpec"] | None = None


class CmvmStep(NamedTuple):
    """One CMVM step of a design's pipeline, as ``CompiledDesign.cmvm_steps``
    lists them."""

    step: int  # index of the top-level step whose step{i}_{kind} scope runs it
    kind: str  # "dense" or "conv"
    executor: str  # "dot" (one exact MXU dot), "conv" (one exact convolution) or "adder_graph"
    instances: int  # CMVM instances an event
    macs: int  # multiply-accumulates an event: instances x n_in x n_out


@dataclass
class CompiledDesign:
    steps: list[Callable] = field(default_factory=list)
    reports: list[LayerReport] = field(default_factory=list)
    in_quant: QuantConfig | None = None
    in_shape: tuple = ()
    out_shape: tuple = ()
    out_qints: list[QInterval] = field(default_factory=list)
    # solve-phase accounting: n_solves / n_cache_hits / n_pool_solves /
    # pool_fallback (why the solve pool went serial, None when it ran) /
    # solver_time_s (sum over unique CMVMs, ~0 when everything hits cache)
    solver_stats: dict = field(default_factory=dict)
    # declarative pipeline: step specs + per-unique-CMVM instruction
    # tables + packed DAIS programs (``DAISProgram.to_arrays`` dicts; an
    # entry is None when a program's qints exceed int64 and cannot be
    # serialized).  ``steps`` is always built from these via build_steps.
    step_specs: list[StepSpec] = field(default_factory=list)
    tables: list = field(default_factory=list)
    programs: list = field(default_factory=list)
    use_pallas: bool = False
    # the CompileConfig that produced this design (embedded in saved
    # artifact manifests; None for designs loaded from pre-config
    # artifacts or built by hand)
    config: CompileConfig | None = None

    # ------------------------------------------------------------------
    def save(self, path):
        """Persist this design as a ``da4ml-design`` artifact directory
        (see :func:`repro.runtime.save_design`); the compile config is
        embedded in the manifest."""
        from ..runtime.artifact import save_design  # lazy: runtime imports nn

        return save_design(self, path)

    @classmethod
    def load(
        cls, path, verify: str = "off", on_corrupt: str = "raise"
    ) -> "CompiledDesign":
        """Rebuild a design from a ``save_design`` artifact — millisecond
        cold start, zero CMVM solves, bit-identical execution.  ``verify``
        optionally runs the static verifier on the rebuilt design;
        ``on_corrupt="quarantine"`` moves a damaged artifact aside before
        raising :class:`repro.runtime.ArtifactCorruptError`."""
        from ..runtime.artifact import load_design  # lazy: runtime imports nn

        return load_design(path, verify=verify, on_corrupt=on_corrupt)

    @property
    def total_adders(self) -> int:
        return sum(r.adders for r in self.reports)

    @property
    def total_cost_bits(self) -> int:
        return sum(r.cost_bits for r in self.reports)

    @property
    def total_ff_bits(self) -> int:
        return sum(r.ff_bits for r in self.reports)

    @property
    def latency_cycles(self) -> int:
        return sum(r.stages for r in self.reports)

    @property
    def max_depth(self) -> int:
        return max((r.depth for r in self.reports), default=0)

    # ------------------------------------------------------------------
    def forward_int(self, x_int: jnp.ndarray) -> jnp.ndarray:
        """Run the integer pipeline. x_int: [batch, *in_shape] grid ints.

        Each step runs under ``jax.named_scope(f"step{i}_{kind}")``, which
        names its ops in the compiled program's ``op_name`` metadata."""
        v = x_int.reshape(x_int.shape[0], -1).astype(jnp.int32)
        for i, (spec, step) in enumerate(zip(self.step_specs, self.steps, strict=True)):
            with jax.named_scope(f"step{i}_{spec.kind}"):
                v = step(v)
        return v.reshape(x_int.shape[0], *self.out_shape)

    def forward(self, x: jnp.ndarray) -> jnp.ndarray:
        """Float-in/float-out wrapper around the integer pipeline."""
        assert self.in_quant is not None
        q = self.in_quant
        xi = jnp.clip(jnp.floor(x / q.step), q.qint.lo, q.qint.hi).astype(jnp.int32)
        y = self.forward_int(xi)
        exps = np.array([q_.exp if not q_.is_zero else 0 for q_ in self.out_qints])
        return y.astype(jnp.float32) * (2.0 ** exps).reshape(self.out_shape)

    @property
    def cmvm_steps(self) -> list[CmvmStep]:
        """Each CMVM step in pipeline order: the index of the top-level
        step it runs under (a residual's body steps give the residual's),
        its kind, its executor, its instances and its MACs an event."""
        mats = _dot_matrices(self.tables, self.programs, self.use_pallas)
        out = []
        for i, s, n in _cmvm_instances(
            self.step_specs, self.tables, int(np.prod(self.in_shape))
        ):
            tab = self.tables[s.table]
            ex = "adder_graph" if mats[s.table] is None else "conv" if s.kind == "conv" else "dot"
            out.append(CmvmStep(i, s.kind, ex, n, n * tab.n_inputs * tab.n_outputs))
        return out

    @property
    def executors(self) -> list[str]:
        """Each CMVM step's executor, in pipeline order: "dot" (one exact
        MXU dot, see ``build_steps``), "conv" (a conv step's one exact
        convolution) or "adder_graph"."""
        return [s.executor for s in self.cmvm_steps]

    @property
    def dot_share(self) -> float:
        """MACs of the CMVM steps that run on the MXU, as a "dot" or a
        "conv", over all CMVM MACs (1.0 for a design with no CMVM step).
        A conv step's MACs are the same under either executor, so the
        share does not depend on which of the two runs it."""
        steps = self.cmvm_steps
        total = sum(s.macs for s in steps)
        on_mxu = sum(s.macs for s in steps if s.executor != "adder_graph")
        return on_mxu / total if total else 1.0

    def summary(self) -> str:
        hdr = (
            f"{'layer':<20}{'shape':<14}{'adders':>8}{'LUTbits':>9}{'depth':>7}"
            f"{'stages':>7}{'FFbits':>8}{'t[s]':>8}{'MACs/ev':>10}{'exec':>13}"
        )
        rows = [hdr, "-" * len(hdr)]
        steps = self.cmvm_steps
        for k, r in enumerate(self.reports):
            macs, ex = (steps[k].macs, steps[k].executor) if k < len(steps) else ("?", "?")
            rows.append(
                f"{r.name:<20}{r.shape:<14}{r.adders:>8}{r.cost_bits:>9}{r.depth:>7}"
                f"{r.stages:>7}{r.ff_bits:>8}{r.solver_time_s:>8.2f}{macs:>10}{ex:>13}"
            )
        rows.append("-" * len(hdr))
        rows.append(
            f"{'TOTAL':<20}{'':<14}{self.total_adders:>8}{self.total_cost_bits:>9}"
            f"{self.max_depth:>7}{self.latency_cycles:>7}{self.total_ff_bits:>8}"
        )
        return "\n".join(rows)


# ----------------------------------------------------------------------
# Step builder: StepSpec -> executable jnp callable
# ----------------------------------------------------------------------
def build_steps(
    specs: list[StepSpec], tables: list, use_pallas: bool = False, programs: list | None = None
):
    """Construct the executable pipeline from declarative step specs.

    ``tables``: the design's per-unique-CMVM ``AdderGraphTables`` list;
    ``programs``: its packed DAIS programs, one per table.  Both
    ``compile_model`` and the artifact loader go through this single
    builder, which is what makes save->load bit-exact.

    A CMVM step whose table ``repro.kernels.adder_graph.dot.dot_matrix``
    proves exact runs as one bfloat16 MXU dot of the matrix its adder
    graph computes, a conv step as one convolution of that matrix over
    its input; every other step (no program for its table, a refused
    proof, or ``use_pallas=True``) runs the adder graph itself, a conv
    step on its im2col of kh·kw strided slices.

    ``use_pallas=True`` runs only on the CPU backend (interpret mode):
    the adder-graph Pallas kernel does not lower through Mosaic, so any
    other backend raises ``NotImplementedError`` here, at build time.
    """
    if use_pallas and jax.default_backend() != "cpu":
        raise NotImplementedError(
            "the adder-graph Pallas kernel (repro.kernels.adder_graph) does not "
            f"compile for the {jax.default_backend()!r} backend; build the design "
            "with use_pallas=False to run the default executors"
        )
    mats = _dot_matrices(tables, programs, use_pallas)
    return [_build_step(s, tables, mats, use_pallas) for s in specs]


def _dot_matrices(tables: list, programs: list | None, use_pallas: bool) -> list:
    """Per table: the integer matrix of its exact dot, or None."""
    if use_pallas or not programs:
        return [None] * len(tables)
    return [dot_matrix(t, p) for t, p in zip(tables, programs, strict=True)]


def _cmvm_instances(specs: list[StepSpec], tables: list, width: int, top=None, out=None):
    """(top-level step index, spec, instances an event) of each CMVM step,
    in pipeline order, for a pipeline fed ``width`` values an event."""
    out = [] if out is None else out
    for i, s in enumerate(specs):
        p, at = s.params, i if top is None else top
        if s.kind in ("dense", "conv"):
            n = width // p["d_in"] if s.kind == "dense" else p["oh"] * p["ow"]
            out.append((at, s, n))
            width = n * tables[s.table].n_outputs
        elif s.kind in ("maxpool", "avgpool"):
            width = (p["h"] // p["ph"]) * (p["w"] // p["pw"]) * p["c"]
        elif s.kind == "residual":
            _cmvm_instances(s.body or [], tables, width, at, out)
    return out


def _shift_bias(spec: StepSpec) -> Callable:
    """The step's own ``y << shift`` then ``+ bias``, over its last axis."""
    bias = (
        jnp.asarray(spec.arrays["bias"], jnp.int32) if "bias" in spec.arrays else None
    )
    shift = (
        jnp.asarray(np.asarray(spec.arrays["shift"])[None, :], jnp.int32)
        if "shift" in spec.arrays
        else None
    )

    def tail(y, bias=bias, shift=shift):
        if shift is not None:
            y = y << shift
        return y + bias if bias is not None else y

    return tail


def _build_cmvm_fn(spec: StepSpec, tables: list, mats: list, use_pallas: bool):
    tab, mat = tables[spec.table], mats[spec.table]
    if mat is not None:
        mat = jnp.asarray(mat, jnp.bfloat16)
    tail = _shift_bias(spec)

    def cmvm(v, tab=tab, mat=mat, use_pallas=use_pallas):
        if mat is None:
            return tail(adder_graph_apply(tab, v, use_pallas=use_pallas))
        return tail(cmvm_dot(mat, v))

    return cmvm


def _build_step(spec: StepSpec, tables: list, mats: list, use_pallas: bool) -> Callable:
    kind, p = spec.kind, spec.params
    if kind == "dense":
        f = _build_cmvm_fn(spec, tables, mats, use_pallas)

        # the CMVM sees the [B, instances, d_in] view: the dot contracts
        # its last axis in place, where a flat [B * instances, d_in]
        # operand pads a narrow d_in out to the TPU's 128 lanes
        def step(v, d_in=p["d_in"], f=f):
            n = v.shape[0]
            return f(v.reshape(n, -1, d_in)).reshape(n, -1)

        return step
    if kind == "conv":
        h, w, cin = p["h"], p["w"], p["cin"]
        kh, kw, sh, sw = p["kh"], p["kw"], p["sh"], p["sw"]
        oh, ow = p["oh"], p["ow"]
        mat = mats[spec.table]
        if mat is not None:
            # a proven table runs as one convolution: its rows are the
            # im2col columns in their (dy, dx, c) order, so they reshape
            # to the HWIO kernel, and each window's sum is the dot's
            kernel = jnp.asarray(mat.reshape(kh, kw, cin, -1), jnp.bfloat16)
            tail = _shift_bias(spec)

            def step(v, h=h, w=w, cin=cin, sh=sh, sw=sw, kernel=kernel, tail=tail):
                y = tail(cmvm_conv(kernel, v.reshape(-1, h, w, cin), (sh, sw)))
                return y.reshape(y.shape[0], -1)  # [B, oh*ow*cout]

            return step
        f = _build_cmvm_fn(spec, tables, mats, use_pallas)

        def step(v, h=h, w=w, cin=cin, kh=kh, kw=kw, sh=sh, sw=sw, oh=oh, ow=ow, f=f):
            x = v.reshape(-1, h, w, cin)
            patches = [
                x[:, dy : dy + sh * (oh - 1) + 1 : sh, dx : dx + sw * (ow - 1) + 1 : sw, :]
                for dy in range(kh)
                for dx in range(kw)
            ]
            cols = jnp.concatenate(patches, axis=-1)  # [B, oh, ow, kh*kw*cin]
            y = f(cols.reshape(-1, oh * ow, kh * kw * cin))
            return y.reshape(-1, oh * ow * y.shape[-1])

        return step
    if kind == "requant":
        d = np.asarray(spec.arrays["d"], np.int64)

        def step(v, d=d, lo=p["lo"], hi=p["hi"]):
            dpos = jnp.asarray(np.maximum(d, 0)[None, :], jnp.int32)
            dneg = jnp.asarray(np.maximum(-d, 0)[None, :], jnp.int32)
            v = jnp.where(dpos > 0, v << dpos, v >> dneg)
            return jnp.clip(v, lo, hi)

        return step
    if kind == "transpose":
        _shape, _perm = tuple(p["shape"]), tuple(p["perm"])

        def step(v, shape=_shape, perm=_perm):
            n = v.shape[0]
            return v.reshape(n, *shape).transpose(0, *[q + 1 for q in perm]).reshape(n, -1)

        return step
    if kind == "relu":
        return lambda v: jnp.maximum(v, 0)
    if kind in ("maxpool", "avgpool"):
        h, w, c, ph, pw = p["h"], p["w"], p["c"], p["ph"], p["pw"]

        # floor pooling: a remainder row or column (h % ph, w % pw) is
        # dropped, as the qint walk drops it; a tiling window crops nothing
        def step(v, h=h, w=w, c=c, ph=ph, pw=pw, is_max=(kind == "maxpool")):
            n, oh, ow = v.shape[0], h // ph, w // pw
            x = v.reshape(n, h, w, c)[:, : oh * ph, : ow * pw]
            x = x.reshape(n, oh, ph, ow, pw, c)
            r = x.max(axis=(2, 4)) if is_max else x.sum(axis=(2, 4))
            return r.reshape(n, -1)

        return step
    if kind == "residual":
        body = tuple(_build_step(s, tables, mats, use_pallas) for s in spec.body or [])
        sa = jnp.asarray(np.asarray(spec.arrays["sa"])[None, :], jnp.int32)
        sb = jnp.asarray(np.asarray(spec.arrays["sb"])[None, :], jnp.int32)

        def step(v, body=body, sa=sa, sb=sb):
            u = v
            for s in body:
                u = s(u)
            return (v << sa) + (u << sb)

        return step
    raise ValueError(f"unknown step kind {kind!r}")


# ----------------------------------------------------------------------
# qint helpers
# ----------------------------------------------------------------------
def _relu_qint(q: QInterval) -> QInterval:
    if q.is_zero:
        return q
    return QInterval(max(q.lo, 0), max(q.hi, 0), q.exp)


def _requant_qint(q: QInterval, cfg: QuantConfig) -> QInterval:
    """floor+saturate of a value with interval q onto cfg's grid."""
    t = cfg.qint
    if q.is_zero:
        return QInterval(0, 0, t.exp)
    d = q.exp - t.exp
    lo = q.lo << d if d >= 0 else q.lo >> (-d)
    hi = q.hi << d if d >= 0 else q.hi >> (-d)
    lo = min(max(lo, t.lo), t.hi)
    hi = min(max(hi, t.lo), t.hi)
    return QInterval(lo, hi, t.exp)


def _union_all(qs: list[QInterval]) -> QInterval:
    q0 = qs[0]
    if all(q is q0 or q == q0 for q in qs):
        return q0
    for q in qs[1:]:
        q0 = q0.union(q)
    return q0


def _exps(qints: list[QInterval], fallback: int = 0) -> np.ndarray:
    return np.array([fallback if q.is_zero else q.exp for q in qints], dtype=np.int64)


def _requant_spec(qints: list[QInterval], cfg: QuantConfig) -> StepSpec:
    t = cfg.qint
    d = _exps(qints, fallback=t.exp) - t.exp
    # "exp" (the target grid exponent) is not read by the executor; it is
    # the metadata that lets the static verifier (repro.analysis) replay
    # this requant's interval transfer exactly
    return StepSpec(
        "requant",
        params={"lo": int(t.lo), "hi": int(t.hi), "exp": int(t.exp)},
        arrays={"d": d},
    )


def _align_exps(qints_a, qints_b):
    """Shift arrays onto the common (finer) per-feature grid + summed qints."""
    ea, eb = _exps(qints_a), _exps(qints_b)
    e = np.minimum(ea, eb)
    out_q = []
    for qa, qb, ee in zip(qints_a, qints_b, e):
        qa2 = QInterval(qa.lo, qa.hi, qa.exp) if not qa.is_zero else QInterval(0, 0, int(ee))
        qb2 = QInterval(qb.lo, qb.hi, qb.exp) if not qb.is_zero else QInterval(0, 0, int(ee))
        out_q.append(qa2.add(qb2))
    return (ea - e).astype(np.int64), (eb - e).astype(np.int64), out_q


# ----------------------------------------------------------------------
# Compiler
# ----------------------------------------------------------------------
# compile_model runs in three phases:
#
#   plan    walk the layer graph, quantize weights, and propagate exact
#           per-feature qints WITHOUT solving: the output interval of a
#           CMVM is the exact affine range of y = x @ W (structure-
#           independent), so downstream layers can be planned before any
#           solver runs.  Each unique (matrix, qints, dc, strategy) is
#           registered once as a _SolveSlot.
#   solve   resolve the slots: content-addressed cache first, then the
#           remaining solves either serially or on a GIL-releasing
#           thread pool (``jobs=``; the solver hot loop is pure numpy).
#           Results stitch back by slot identity, so the parallel path
#           is bit-identical to the serial one.
#   stitch  compile instruction tables, pipeline reports, and layer
#           reports in original layer order.


class _SolveSlot:
    """One deferred CMVM solve.  After stitch, everything except the
    compiled instruction tables is released (apply_fn closures keep the
    slot alive for the design's lifetime, and the weight matrices /
    solved programs would otherwise be pinned along with it)."""

    __slots__ = (
        "w_int", "qin", "strategy", "solver_cfg", "key", "solution", "tables", "idx",
    )

    def __init__(self, w_int, qin, strategy, solver_cfg, idx):
        self.w_int = w_int
        self.qin = qin
        self.strategy = strategy
        self.solver_cfg: SolverConfig = solver_cfg
        self.key = None
        self.solution: Solution | None = None
        self.tables = None
        self.idx = idx  # position in ctx.slots == design.tables index


class _Ctx:
    def __init__(self, cfg: CompileConfig, design):
        self.cfg = cfg
        self.strategy = cfg.strategy
        self.mdps = cfg.max_delay_per_stage
        self.design = design
        self._solver_digest = cfg.solver.digest()
        self.slots: list[_SolveSlot] = []
        self.slot_map: dict = {}
        self.pending_reports: list = []

    def request(self, w_int: np.ndarray, qin: list[QInterval]) -> _SolveSlot:
        dedup = (
            self.strategy, self._solver_digest,
            w_int.shape, w_int.tobytes(), tuple(qin),
        )
        slot = self.slot_map.get(dedup)
        if slot is None:
            slot = _SolveSlot(w_int, qin, self.strategy, self.cfg.solver, len(self.slots))
            self.slot_map[dedup] = slot
            self.slots.append(slot)
        return slot


def _slot_key(slot: _SolveSlot) -> str:
    """Cache key; matches solve_cmvm's internal key for the "da" path
    (both derive from the SolverConfig digest, so they cannot drift)."""
    depth_in = [0] * len(slot.qin)
    if slot.strategy == "latency":
        return solve_key(slot.w_int, slot.qin, depth_in, kind="latency")
    return config_solve_key(slot.w_int, slot.qin, depth_in, slot.solver_cfg)


def _solve_slots(
    slots: list[_SolveSlot],
    jobs: int | None,
    cache: SolutionCache | None,
    slot_names: dict[int, list[str]] | None = None,
) -> dict:
    """Resolve the deferred CMVM solves: cache first, then the remaining
    misses in a thread pool.

    Versus the process pool this replaces there is no fork/spawn
    startup and no payload pickling (the old pool serialized every
    weight matrix twice and paid ~1s of interpreter spin-up, which
    dominated small-layer compiles).  numpy drops the GIL inside its
    kernels but the solver's Python-level bookkeeping still serializes
    part of each solve, so the thread speedup is sublinear — on boxes
    with little parallel headroom ``jobs=1`` wins outright (see
    docs/solver_performance.md for measurements).  Each worker thread
    keeps its own ``CSEArena`` (see repro.core.cse), so
    ``engine="arena"`` solves stay allocation-quiet across layers.
    Results stitch back by slot identity: any ``jobs`` value is
    bit-identical to serial.

    Going serial is never silent: ``pool_fallback`` in the returned
    stats records why the pool was skipped (None when it actually ran).
    """
    t0 = time.perf_counter()
    cache_before = cache.stats.as_dict() if cache is not None else None
    names = slot_names or {}
    slot_wall: dict[int, float] = {}
    slot_hit: dict[int, bool] = {}
    n_hits = 0
    misses: list[_SolveSlot] = []
    for slot in slots:
        if cache is not None:
            th0 = time.perf_counter()
            slot.key = _slot_key(slot)
            hit = cache.get(slot.key)
            if hit is not None:
                slot.solution = hit
                slot_wall[slot.idx] = time.perf_counter() - th0
                slot_hit[slot.idx] = True
                n_hits += 1
                continue
        misses.append(slot)
    n_pool = 0
    fallback: str | None = None
    if misses:
        # (payload, label) units: the label names the solve's trace span
        # and keys the per-slot wall time (satellite per-layer stats)
        work = [
            (
                (s.w_int, s.qin, s.strategy, s.solver_cfg.to_dict()),
                names.get(s.idx, [f"slot{s.idx}"])[0],
            )
            for s in misses
        ]
        results: list[tuple[Solution, float]] | None = None
        jobs_eff = os.cpu_count() or 1 if jobs is None else jobs
        if jobs_eff == 1:
            fallback = "jobs=1"
        elif len(misses) == 1:
            fallback = "single_solve"
        else:
            workers = min(jobs_eff, len(misses))
            try:
                with concurrent.futures.ThreadPoolExecutor(
                    workers, thread_name_prefix="da4ml-solve"
                ) as ex:
                    results = list(ex.map(_timed_solve_task, work))
                n_pool = len(results)
            except Exception as e:  # pool unavailable: loud serial fallback
                results = None
                fallback = f"thread_pool_error: {type(e).__name__}: {e}"
        if results is None:
            results = [_timed_solve_task(w) for w in work]
        for slot, (sol, wall) in zip(misses, results):
            slot.solution = sol
            slot_wall[slot.idx] = wall
            slot_hit[slot.idx] = False
            if cache is not None:
                cache.put(slot.key, sol)
    else:
        fallback = "no_cache_misses" if slots else "no_cmvm_layers"
    stats = {
        "n_solves": len(misses),
        "n_cache_hits": n_hits,
        "n_pool_solves": n_pool,
        "pool_fallback": fallback,
        "solver_time_s": sum(s.solution.solver_time_s for s in slots),
        "solve_phase_s": time.perf_counter() - t0,
        "per_layer": _per_layer_stats(slots, names, slot_wall, slot_hit),
    }
    if cache is not None:
        # per-compile delta of the cache counters (hits/misses/puts/
        # disk_hits/...), so artifact-vs-cache savings are measurable
        # even when one SolutionCache is shared across compiles.
        after = cache.stats.as_dict()
        stats["cache_stats"] = {k: after[k] - cache_before[k] for k in after}
    return stats


def _timed_solve_task(work: tuple) -> tuple[Solution, float]:
    """One pool unit: solve + wall time, under a labelled trace span so
    the Perfetto timeline shows which layer each pool thread solved."""
    payload, label = work
    t0 = time.perf_counter()
    with trace.span("compile.solve", layer=label):
        sol = solve_task(payload)
    return sol, time.perf_counter() - t0


def _per_layer_stats(
    slots: list[_SolveSlot],
    names: dict[int, list[str]],
    slot_wall: dict[int, float],
    slot_hit: dict[int, bool],
) -> dict:
    """Per-layer solve attribution: wall seconds and cache hit/miss keyed
    by layer name (layers deduplicated onto one slot each get an entry
    pointing at the shared slot)."""
    per_layer: dict[str, dict] = {}
    for slot in slots:
        layer_names = names.get(slot.idx, [f"slot{slot.idx}"])
        sol = slot.solution
        for nm in layer_names:
            per_layer[nm] = {
                "slot": slot.idx,
                "shape": f"{slot.w_int.shape[0]}x{slot.w_int.shape[1]}"
                if slot.w_int is not None
                else "?",
                "cache_hit": slot_hit.get(slot.idx, False),
                "solve_wall_s": slot_wall.get(slot.idx, 0.0),
                "adders": int(sol.n_adders) if sol is not None else 0,
                "cost_bits": int(sol.cost_bits) if sol is not None else 0,
                "depth": int(sol.depth) if sol is not None else 0,
                "shared_slot": len(layer_names) > 1,
            }
    return per_layer


# legacy kwarg name -> how it maps into CompileConfig
_LEGACY_COMPILE_DEFAULTS = {
    "dc": 2,
    "strategy": "da",
    "max_delay_per_stage": 5,
    "use_pallas": False,
    "jobs": None,
    "cache": None,
    "engine": "batch",
}


def compile_model(
    model: Sequential,
    params: list,
    in_shape: tuple[int, ...],
    in_quant: QuantConfig,
    dc=UNSET,
    strategy=UNSET,
    max_delay_per_stage=UNSET,
    use_pallas=UNSET,
    jobs=UNSET,
    cache=UNSET,
    engine=UNSET,
    config: CompileConfig | None = None,
) -> CompiledDesign:
    """Compile a quantized Sequential into a bit-exact integer design.

    The canonical way to set options is ``config=``, a
    :class:`repro.flow.CompileConfig` (this is what ``Flow.compile``
    passes).  The individual option kwargs are a deprecated shim kept
    for one release: they construct the equivalent config and delegate,
    so both spellings produce bit-identical designs.

    Config highlights — ``strategy`` ("da" solver / "latency" baseline);
    ``jobs`` (CMVM solver thread-pool width: None = cpu_count, 1 =
    serial; any value is bit-identical, and serial fallbacks are
    recorded in ``solver_stats["pool_fallback"]``); ``cache`` (a
    :class:`SolutionCache` so repeated compiles skip solved CMVMs
    entirely); ``solver`` (nested :class:`SolverConfig`: dc, CSE engine
    — "arena" reuses per-thread workspaces across layers — and scoring
    knobs; compile default dc=2).
    """
    legacy = {
        name: val
        for name, val in (
            ("dc", dc),
            ("strategy", strategy),
            ("max_delay_per_stage", max_delay_per_stage),
            ("use_pallas", use_pallas),
            ("jobs", jobs),
            ("cache", cache),
            ("engine", engine),
        )
        if val is not UNSET
    }
    config = resolve_legacy(
        "compile_model", config, legacy, CompileConfig, _config_from_legacy
    )
    return _compile_model(model, params, in_shape, in_quant, config)


def _config_from_legacy(legacy: dict) -> CompileConfig:
    def get(k):
        return legacy.get(k, _LEGACY_COMPILE_DEFAULTS[k])

    return CompileConfig(
        strategy=get("strategy"),
        max_delay_per_stage=get("max_delay_per_stage"),
        use_pallas=get("use_pallas"),
        jobs=get("jobs"),
        cache=get("cache"),
        solver=SolverConfig(dc=get("dc"), engine=get("engine")),
    )


def _compile_model(
    model: Sequential,
    params: list,
    in_shape: tuple[int, ...],
    in_quant: QuantConfig,
    cfg: CompileConfig,
) -> CompiledDesign:
    """Config-consuming compiler core (all public paths delegate here)."""
    if not isinstance(cfg, CompileConfig):
        from ..flow.config import ConfigError

        raise ConfigError(
            f"compile_model: config must be a CompileConfig, got {type(cfg).__name__}"
        )
    design = CompiledDesign(
        in_quant=in_quant, in_shape=tuple(in_shape), use_pallas=cfg.use_pallas,
        # the design keeps the config *identity*, not the live cache
        # handle (runtime-only; storing it would pin every cached entry
        # for the design's lifetime — and load_design can't restore it)
        config=cfg.replace(cache=None),
    )
    ctx = _Ctx(cfg, design)
    shape = tuple(in_shape)
    qints = [in_quant.qint] * int(np.prod(shape))
    # plan
    with trace.span("compile.plan", n_layers=len(model)):
        specs, shape, qints = _compile_seq(model, params, shape, qints, ctx)
    # slot -> unique layer names ("dense0", "conv1", ... in layer order);
    # layers deduplicated onto one slot contribute one name each
    slot_names: dict[int, list[str]] = {}
    for k, (slot, name, _shape_str, _nb, _bb) in enumerate(ctx.pending_reports):
        slot_names.setdefault(slot.idx, []).append(f"{name}{k}")
    # solve
    with trace.span("compile.solve_phase", n_slots=len(ctx.slots)):
        design.solver_stats = _solve_slots(ctx.slots, cfg.jobs, cfg.cache, slot_names)
    design.solver_stats["engine"] = cfg.solver.engine
    # stitch
    _stitch_span = trace.span("compile.stitch")
    _stitch_span.__enter__()
    for slot, name, shape_str, n_bias, bias_bits in ctx.pending_reports:
        sol = slot.solution
        if slot.tables is None:
            slot.tables = compile_tables(sol.program)
        rep = pipeline(sol.program, ctx.mdps)
        design.reports.append(
            LayerReport(
                name=f"{name}[{ctx.strategy}]",
                shape=shape_str,
                adders=sol.n_adders + n_bias,
                cost_bits=sol.cost_bits + bias_bits,
                depth=sol.depth + (1 if n_bias else 0),
                stages=rep.n_stages,
                ff_bits=rep.ff_bits,
                solver_time_s=sol.solver_time_s,
            )
        )
    n_packs = 0
    n_reused = 0
    for slot in ctx.slots:
        if slot.tables is None:
            slot.tables = compile_tables(slot.solution.program)
        design.tables.append(slot.tables)
        # prefer the SolutionCache's already-packed arrays (set on both
        # cache hits and puts) over a fresh to_arrays pack; warm-cache
        # compiles therefore perform zero repacks (n_program_packs == 0)
        parr = slot.solution.program_arrays
        if parr is not None:
            design.programs.append(parr)
            n_reused += 1
        else:
            try:
                design.programs.append(slot.solution.program.to_arrays())
                n_packs += 1
            except OverflowError:
                design.programs.append(None)  # not serializable: save_design rejects
        slot.w_int = slot.qin = slot.solution = slot.key = None
    design.solver_stats["n_program_packs"] = n_packs
    design.solver_stats["n_program_arrays_reused"] = n_reused
    design.step_specs = specs
    design.steps = build_steps(specs, design.tables, cfg.use_pallas, design.programs)
    design.out_shape = shape
    design.out_qints = qints
    _stitch_span.__exit__(None, None, None)
    if cfg.verify != "off":
        _verify_design_gate(design, cfg, slot_names)
    return design


def _verify_design_gate(design: CompiledDesign, cfg: CompileConfig, slot_names) -> None:
    """Run the static verifier on a freshly compiled design.

    Findings land in ``solver_stats["verify"]`` (overall + per-layer
    pass/fail and wall time, keyed by the same layer names as
    ``per_layer`` solve stats); error-severity findings raise
    ``repro.analysis.DesignVerificationError`` — a design the verifier
    rejects must not be silently returned.
    """
    from ..analysis import DesignVerificationError, verify_design  # lazy: no cycle

    t0 = time.perf_counter()
    with trace.span("analysis.verify", tier=cfg.verify):
        vrep = verify_design(
            design, tier=cfg.verify, max_delay_per_stage=cfg.max_delay_per_stage
        )
    wall = time.perf_counter() - t0
    by_prog = vrep.pass_wall_s.get("program_by_index", {})
    per_layer = {}
    for idx, names in slot_names.items():
        n_err = sum(
            1 for d in vrep.errors if d.loc.get("program") == idx
        )
        for nm in names:
            per_layer[nm] = {
                "ok": n_err == 0,
                "n_errors": n_err,
                "wall_s": by_prog.get(idx, 0.0),
            }
    design.solver_stats["verify"] = {
        "tier": cfg.verify,
        "ok": vrep.ok,
        "n_errors": len(vrep.errors),
        "n_warnings": len(vrep.warnings),
        "wall_s": wall,
        "pass_wall_s": {
            k: v for k, v in vrep.pass_wall_s.items() if isinstance(v, float)
        },
        "per_layer": per_layer,
    }
    if not vrep.ok:
        raise DesignVerificationError(vrep, context="compiled design")


def _affine_out_qints(w_int: np.ndarray, qin: list[QInterval]) -> list[QInterval]:
    """Exact per-output intervals of y = x @ w_int.

    The adder graph computes each output exactly, so its value range is
    the affine-form interval — independent of how the solver structures
    the computation.  This is what lets the plan phase propagate qints
    through the network before any CMVM is solved (and it is never wider
    than interval propagation through the adder tree)."""
    out: list[QInterval] = []
    for jcol in range(w_int.shape[1]):
        q: QInterval | None = None
        col = w_int[:, jcol]
        for i in np.nonzero(col)[0]:
            term = qin[int(i)].scale(int(col[i]))
            q = term if q is None else q.add(term)
        out.append(QInterval(0, 0, 0) if q is None else q)
    return out


def _cmvm(name, w, b, wq: QuantConfig, qin: list[QInterval], ctx: _Ctx):
    """Plan one CMVM + bias. Returns ((table_idx, arrays), out_qints)
    for a cmvm-kind StepSpec; the solve itself is deferred to a
    _SolveSlot."""
    w_int = np.clip(
        np.round(np.asarray(w, np.float64) / wq.step), wq.qint.lo, wq.qint.hi
    ).astype(np.int64)
    we = wq.scale_exp()
    slot = ctx.request(w_int, list(qin))
    out_qints = [q.shift(we) for q in _affine_out_qints(w_int, qin)]

    b_int = None
    pre_shift = None
    if b is not None:
        # bias lives on the accumulator grid e_b = in_exp + w_exp; outputs
        # whose qint landed on a coarser grid are shifted down to the
        # common grid first (wiring, not logic).
        e_b = we + min(q.exp for q in qin)
        exps = _exps(out_qints, fallback=e_b)
        tgt = np.minimum(exps, e_b)
        pre_shift = (exps - tgt).astype(np.int64)
        b_int = np.floor(np.asarray(b, np.float64) / (2.0 ** tgt) + 0.5).astype(np.int64)
        out_qints = [
            QInterval((q.lo << int(s)) + int(bi), (q.hi << int(s)) + int(bi), int(t))
            if not q.is_zero
            else QInterval(min(int(bi), 0), max(int(bi), 0), int(t))
            for q, bi, s, t in zip(out_qints, b_int, pre_shift, tgt)
        ]

    n_bias = int(np.count_nonzero(b_int)) if b_int is not None else 0
    bias_bits = (
        sum(q.width for q, bi in zip(out_qints, b_int) if bi) if b_int is not None else 0
    )
    ctx.pending_reports.append(
        (slot, name, f"{w_int.shape[0]}x{w_int.shape[1]}", n_bias, bias_bits)
    )

    arrays: dict = {}
    if b_int is not None:
        arrays["bias"] = np.asarray(b_int, np.int64)
    if pre_shift is not None and pre_shift.any():
        arrays["shift"] = np.asarray(pre_shift, np.int64)
    return (slot.idx, arrays), out_qints


def _compile_seq(model, params, shape, qints, ctx):
    specs: list[StepSpec] = []
    for spec, p in zip(model, params):
        if isinstance(spec, QDense):
            s, shape, qints = _compile_dense_last(spec, p, shape, qints, ctx)
            specs.append(s)
            if spec.out_quant is not None:
                specs.append(_requant_spec(qints, spec.out_quant))
                qints = [_requant_qint(q, spec.out_quant) for q in qints]
        elif isinstance(spec, QDenseOnAxis):
            ax = spec.axis % len(shape)
            perm = [i for i in range(len(shape)) if i != ax] + [ax]
            inv = np.argsort(perm).tolist()
            pshape = tuple(shape[i] for i in perm)
            specs.append(StepSpec("transpose", params={"shape": list(shape), "perm": perm}))
            qints_t = _transpose_qints(qints, shape, perm)
            inner = QDense(spec.units, spec.w_quant, None, spec.use_bias)
            s, pshape2, qints_t = _compile_dense_last(inner, p, pshape, qints_t, ctx)
            specs.append(s)
            specs.append(
                StepSpec("transpose", params={"shape": list(pshape2), "perm": inv})
            )
            shape = tuple(pshape2[i] for i in inv)
            qints = _transpose_qints(qints_t, pshape2, inv)
            if spec.out_quant is not None:
                specs.append(_requant_spec(qints, spec.out_quant))
                qints = [_requant_qint(q, spec.out_quant) for q in qints]
        elif isinstance(spec, QConv2D):
            s, shape, qints = _compile_conv(spec, p, shape, qints, ctx)
            specs.append(s)
            if spec.out_quant is not None:
                specs.append(_requant_spec(qints, spec.out_quant))
                qints = [_requant_qint(q, spec.out_quant) for q in qints]
        elif isinstance(spec, ReLU):
            specs.append(StepSpec("relu"))
            qints = [_relu_qint(q) for q in qints]
            if spec.out_quant is not None:
                specs.append(_requant_spec(qints, spec.out_quant))
                qints = [_requant_qint(q, spec.out_quant) for q in qints]
        elif isinstance(spec, MaxPool2D):
            s, shape, qints = _compile_maxpool(spec, shape, qints)
            specs.append(s)
        elif isinstance(spec, AvgPool2D):
            s, shape, qints = _compile_avgpool(spec, shape, qints)
            specs.append(s)
        elif isinstance(spec, Flatten):
            shape = (int(np.prod(shape)),)
        elif isinstance(spec, Residual):
            body_specs, bshape, bq = _compile_seq(spec.body, p["body"], shape, qints, ctx)
            assert bshape == shape, "residual body must preserve shape"
            sa, sb, qints = _align_exps(qints, bq)
            specs.append(
                StepSpec("residual", arrays={"sa": sa, "sb": sb}, body=body_specs)
            )
        else:
            raise TypeError(f"cannot compile {spec}")
    return specs, shape, qints


def _compile_dense_last(spec: QDense, p, shape, qints, ctx):
    d_in = shape[-1]
    lead = int(np.prod(shape[:-1]))
    # union input qints across leading positions (shared CMVM instance)
    qarr = np.array(qints, dtype=object).reshape(lead, d_in)
    qin = [_union_all(list(qarr[:, k])) for k in range(d_in)]
    b = np.asarray(p["b"]) if spec.use_bias else None
    (table, arrays), out_q = _cmvm("dense", np.asarray(p["w"]), b, spec.w_quant, qin, ctx)
    # "wscale" (the weight grid exponent) is verifier metadata, like the
    # requant "exp" param — the executor never reads it
    s = StepSpec(
        "dense",
        params={"d_in": d_in, "wscale": int(spec.w_quant.scale_exp())},
        arrays=arrays,
        table=table,
    )
    return s, shape[:-1] + (spec.units,), list(out_q) * lead


def _transpose_qints(qints, shape, perm):
    arr = np.array(qints, dtype=object).reshape(shape)
    return list(arr.transpose(perm).reshape(-1))


def _pool_spec(kind: str, h, w, c, ph, pw) -> StepSpec:
    return StepSpec(kind, params={"h": h, "w": w, "c": c, "ph": ph, "pw": pw})


def _compile_maxpool(spec: MaxPool2D, shape, qints):
    h, w, c = shape
    ph, pw = spec.size
    oh, ow = h // ph, w // pw

    qarr = np.array(qints, dtype=object).reshape(h, w, c)
    new = []
    for i in range(oh):
        for j in range(ow):
            for ch in range(c):
                block = [
                    qarr[i * ph + a, j * pw + bb, ch] for a in range(ph) for bb in range(pw)
                ]
                new.append(_union_all(block))
    return _pool_spec("maxpool", h, w, c, ph, pw), (oh, ow, c), new


def _compile_avgpool(spec: AvgPool2D, shape, qints):
    """Power-of-two window: avg == sum with exponent shift (exact)."""
    h, w, c = shape
    ph, pw = spec.size
    k = ph * pw
    assert k & (k - 1) == 0
    shift = int(np.log2(k))
    oh, ow = h // ph, w // pw

    qarr = np.array(qints, dtype=object).reshape(h, w, c)
    new = []
    for i in range(oh):
        for j in range(ow):
            for ch in range(c):
                q = None
                for a in range(ph):
                    for bb in range(pw):
                        qq = qarr[i * ph + a, j * pw + bb, ch]
                        q = qq if q is None else q.add(qq)
                new.append(q.shift(-shift))
    return _pool_spec("avgpool", h, w, c, ph, pw), (oh, ow, c), new


def _compile_conv(spec: QConv2D, p, shape, qints, ctx):
    """Conv2D via im2col + shared CMVM (kernel reused spatially)."""
    h, w, cin = shape
    kh, kw = spec.kernel
    sh, sw = spec.strides
    assert spec.padding == "VALID", "compile path supports VALID convs"
    oh = (h - kh) // sh + 1
    ow = (w - kw) // sw + 1

    qarr = np.array(qints, dtype=object).reshape(h, w, cin)
    patch_qints = []
    for dy in range(kh):
        for dx in range(kw):
            for ch in range(cin):
                qs = [
                    qarr[i * sh + dy, j * sw + dx, ch]
                    for i in range(oh)
                    for j in range(ow)
                ]
                patch_qints.append(_union_all(qs))

    wmat = np.asarray(p["w"]).reshape(kh * kw * cin, spec.filters)
    b = np.asarray(p["b"]) if spec.use_bias else None
    (table, arrays), out_q = _cmvm("conv", wmat, b, spec.w_quant, patch_qints, ctx)
    s = StepSpec(
        "conv",
        params={
            "h": h, "w": w, "cin": cin, "kh": kh, "kw": kw,
            "sh": sh, "sw": sw, "oh": oh, "ow": ow,
            "wscale": int(spec.w_quant.scale_exp()),
        },
        arrays=arrays,
        table=table,
    )
    return s, (oh, ow, spec.filters), list(out_q) * (oh * ow)
