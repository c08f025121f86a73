"""JIT'd wrapper + DAIS->instruction-table compiler for the adder-graph
executor (Pallas kernel in kernel.py, pure-jnp oracle in ref.py)."""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from ...core.dais import KIND_ADD, KIND_INPUT, KIND_NEG, DAISProgram


@dataclass(frozen=True)
class AdderGraphTables:
    """Levelized instruction tables.

    instr : int32 [n_ops, 5] — (a_idx, b_idx, sh_a, sh_b, sign), rows
            ordered level-contiguously; ops in level k only reference
            rows produced before level k (inputs occupy rows
            [0, n_inputs)).  Passed to the kernel as a real input.
    level_bounds : static (lo, hi) op ranges per level.
    outs  : int32 [n_out, 4] — (row, shift, sign, mask); mask zeroes the
            constant-0 outputs.
    digest : content hash over every field that determines execution.
            Hash/eq key on it — NOT on identity — so tables rebuilt from
            a saved artifact (or by a second compile of the same model)
            hit the same jit cache entry as the original instead of
            silently re-triggering kernel compilation (``tables`` is a
            static argument of ``adder_graph_pallas``).  The instruction
            arrays are frozen read-only to keep the digest truthful.
    """

    n_inputs: int
    n_rows: int
    level_bounds: tuple[tuple[int, int], ...]
    instr: np.ndarray = field(repr=False)
    outs: np.ndarray = field(repr=False)
    digest: str = ""

    def __post_init__(self):
        if not self.digest:
            object.__setattr__(self, "digest", self._content_digest())
        for arr in (self.instr, self.outs):
            arr.setflags(write=False)

    def _content_digest(self) -> str:
        h = hashlib.sha256(b"adder-graph-tables-v1")
        h.update(np.array([self.n_inputs, self.n_rows], np.int64).tobytes())
        h.update(repr(self.level_bounds).encode())
        h.update(np.ascontiguousarray(self.instr).tobytes())
        h.update(np.ascontiguousarray(self.outs).tobytes())
        return h.hexdigest()

    def __hash__(self):
        return hash(self.digest)

    def __eq__(self, other):
        return isinstance(other, AdderGraphTables) and self.digest == other.digest

    @property
    def n_ops(self) -> int:
        return int(self.instr.shape[0])

    @property
    def n_outputs(self) -> int:
        return int(self.outs.shape[0])


def compile_tables(prog: DAISProgram) -> AdderGraphTables:
    """Reorder a DAIS program level-contiguously and pack instruction
    tables.  Negation rows are lowered onto the same add/sub datapath as
    ``u = (a << 0) - (a << 1) = -a`` (one op, same operand twice)."""
    order = sorted(
        range(len(prog.rows)),
        key=lambda i: (prog.rows[i].kind != KIND_INPUT, prog.rows[i].depth, i),
    )
    remap = {old: new for new, old in enumerate(order)}
    n_inputs = prog.n_inputs

    by_depth: dict[int, list[int]] = {}
    for i in order:
        r = prog.rows[i]
        if r.kind != KIND_INPUT:
            by_depth.setdefault(r.depth, []).append(i)

    instr_rows: list[tuple[int, int, int, int, int]] = []
    bounds: list[tuple[int, int]] = []
    for d in sorted(by_depth):
        lo = len(instr_rows)
        for i in by_depth[d]:
            r = prog.rows[i]
            if r.kind == KIND_ADD:
                instr_rows.append((remap[r.a], remap[r.b], r.sh_a, r.sh_b, r.sign))
            elif r.kind == KIND_NEG:
                instr_rows.append((remap[r.a], remap[r.a], 0, 1, -1))
            else:  # pragma: no cover
                raise AssertionError
        bounds.append((lo, len(instr_rows)))

    instr = np.array(instr_rows, dtype=np.int32).reshape(-1, 5)
    # level-contiguity invariant: operands strictly precede their level
    start = n_inputs
    for lo, hi in bounds:
        if hi > lo:
            assert instr[lo:hi, :2].max() < start
        start += hi - lo

    outs = []
    for t in prog.outputs:
        if t is None:
            outs.append((0, 0, 1, 0))
        else:
            outs.append((remap[t.row], t.shift, t.sign, 1))
    return AdderGraphTables(
        n_inputs=n_inputs,
        n_rows=len(prog.rows),
        level_bounds=tuple(bounds),
        instr=instr,
        outs=np.array(outs, dtype=np.int32).reshape(-1, 4),
    )


def adder_graph_apply(
    tables: AdderGraphTables,
    x: jnp.ndarray,
    *,
    use_pallas: bool = False,
    block_b: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Evaluate y = x @ M through the optimized adder graph.

    x: int array [..., n_inputs] (integer grid). Returns int32
    [..., n_outputs]. ``use_pallas`` selects the Pallas kernel (run in
    interpret mode on the CPU backend, see ``resolve_interpret``); the
    default is the pure-jnp reference, which XLA fuses well on any
    backend.
    """
    from .kernel import adder_graph_pallas
    from .ref import adder_graph_ref

    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if use_pallas:
        y = adder_graph_pallas(tables, x2, block_b=block_b, interpret=interpret)
    else:
        y = adder_graph_ref(tables, x2)
    return y.reshape(*lead, y.shape[-1])
