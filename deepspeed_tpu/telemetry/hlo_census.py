"""Structured census of a compiled XLA program.

The repo grew three disconnected XLA-introspection paths — string-counting
collectives in ``zero/aot_check.py``, a from-scratch recompile in the
flops profiler, and a purely analytic FLOPs formula in ``bench.py``. This
module is the shared substrate all of them now stand on: ONE pass over a
``jax.stages.Compiled`` artifact producing

* compiler cost analysis (flops / transcendentals / bytes accessed);
* compiler memory analysis (argument / output / alias / temp bytes) and
  the derived **HBM watermark** (args + outputs - aliased + temps: the
  static lower bound on live HBM while the program runs);
* a real parse of the post-optimization HLO text extracting every
  collective op with its **result byte volume, replica-group structure,
  and the mesh axis (or axes) it runs over** — replacing
  ``txt.count(op + "(")``, which could neither see bytes nor axes and
  miscounted on substring collisions (``all-gather`` vs
  ``all-gather-start``).

Parsing notes (verified against this jax/XLA's output):

* collective lines look like
  ``%all-reduce.1 = f32[] all-reduce(...), channel_id=5,
  replica_groups=[2,4]<=[8], use_global_device_ids=true, ...``;
* ``replica_groups`` comes in the explicit form ``{{0,4},{1,5}}`` and the
  iota ("v2") form ``[G,S]<=[N]`` with an optional reshape+transpose
  ``[G,S]<=[4,2]T(1,0)`` — all three appear in real programs;
* async pairs (``all-gather-start``/``-done``) describe ONE transfer: the
  ``-start`` is counted, the ``-done`` is not;
* ``collective-permute`` carries ``source_target_pairs`` instead of
  groups.

Everything here is static analysis of an ALREADY-compiled artifact:
calling it never traces, lowers, or compiles anything (``census_fn`` is
the explicit compile-from-scratch fallback for callers with no artifact).
"""

import dataclasses
import re
from typing import Any, Dict, List, Optional, Tuple

# dtype token -> itemsize, per the HLO shape grammar (f8 variants share
# one byte; opaque/token shapes carry no data and parse to 0)
_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8,
    "f8e5m2": 1, "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "f8e3m4": 1, "f8e8m0fnu": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

# one HLO array shape: dtype[dims]{layout}  (layout optional)
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{[^}]*\})?")

_COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                     "all-to-all", "collective-permute",
                     "collective-broadcast", "ragged-all-to-all")

# "%name = SHAPES kind(" where SHAPES is one shape or a (tuple, of, them).
# The kind is matched with lookahead "(" so fused instruction NAMES that
# merely contain a collective substring can't false-positive, and async
# "-start"/"-done" suffixes are captured explicitly.
_COLLECTIVE_LINE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*"
    r"(\([^)]*\)|[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(" + "|".join(re.escape(k) for k in _COLLECTIVE_KINDS) +
    r")(-start|-done)?\(")

_REPLICA_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[^}]*(?:\},\{[^}]*)*\}\}|\{[0-9, ]*\}|"
    r"\[[0-9,]+\]<=\[[0-9,]+\](?:T\([0-9,]+\))?)")

_SOURCE_TARGET_RE = re.compile(r"source_target_pairs=\{([^}]*(?:\},\{[^}]*)*)\}")

_CHANNEL_RE = re.compile(r"channel_id=(\d+)")

_DIM_ATTR_RE = re.compile(r"dimensions=\{(\d+)\}")


def parse_shape_bytes(shape_str: str) -> Tuple[int, List[Tuple[str, Tuple[int, ...]]]]:
    """Total bytes + [(dtype, dims)] of one HLO result shape (array or
    tuple-of-arrays). Unknown dtypes contribute 0 bytes (opaque/token)."""
    elements = _shape_elements(shape_str)
    return (sum(b for _, _, b in elements),
            [(d, s) for d, s, _ in elements])


def _shape_elements(shape_str):
    """[(dtype, dims, bytes)] for each array in an HLO (tuple) shape."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        dim_t = tuple(int(d) for d in dims.split(",") if d != "")
        n = 1
        for d in dim_t:
            n *= d
        out.append((dtype, dim_t, n * _DTYPE_BYTES.get(dtype, 0)))
    return out


def _async_result_bytes(kind, elements):
    """Payload bytes of an async ``-start`` op, whose TUPLE result carries
    the operand(s) alongside the actual result (plus tiny u32/s32 context
    scalars on some backends) — summing the tuple would double count.
    Context scalars are excluded first; the result is then the largest
    element, except reduce-scatter where the result is the 1/g SHARD and
    the largest element is the unreduced input."""
    payload = [b for dtype, dims, b in elements
               if b > 0 and not (len(dims) == 0 and dtype in ("u32", "s32"))]
    if not payload:
        return 0
    if kind == "reduce-scatter":
        return min(payload)
    return max(payload)


def parse_replica_groups(attr: str) -> List[Tuple[int, ...]]:
    """Parse either replica-group syntax into explicit device-id groups.

    Explicit: ``{{0,4},{1,5}}`` (or the degenerate one-group ``{0,1,2}``).
    Iota v2: ``[G,S]<=[N]`` — ids ``0..N-1`` reshaped to [G, S]; the
    optional ``<=[a,b,..]T(p)`` first lays the ids out as [a,b,..],
    transposes by permutation p, then reshapes to [G, S].
    """
    attr = attr.strip()
    if attr.startswith("{"):
        inner = attr.strip("{}")
        if not inner:
            return []
        if "},{" in inner:
            return [tuple(int(x) for x in grp.split(",") if x.strip() != "")
                    for grp in inner.split("},{")]
        return [tuple(int(x) for x in inner.split(",") if x.strip() != "")]
    m = re.match(r"\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?$", attr)
    if not m:
        raise ValueError(f"unrecognised replica_groups syntax: {attr!r}")
    out_shape = [int(x) for x in m.group(1).split(",")]
    src_shape = [int(x) for x in m.group(2).split(",")]
    n = 1
    for d in src_shape:
        n *= d
    try:
        import numpy as np
        ids = np.arange(n).reshape(src_shape)
        if m.group(3):
            ids = ids.transpose([int(x) for x in m.group(3).split(",")])
        ids = ids.reshape(out_shape)
        return [tuple(int(x) for x in row) for row in ids]
    except Exception as e:  # pragma: no cover - numpy is a hard dep anyway
        raise ValueError(f"bad iota replica_groups {attr!r}: {e}")


def _mesh_axis_partitions(mesh) -> Dict[str, frozenset]:
    """For every non-empty subset of mesh axes (sizes > 1), the partition
    of device ids a collective over exactly those axes would use: groups
    vary along the subset's axes and are constant along the rest.

    Returned as {axis-label: frozenset-of-frozenset-groups}; the label is
    the comma-joined axis names ("data" / "data,expert"). Mesh axis count
    is <= ~4 in this repo, so the 2^k subsets stay tiny.
    """
    import itertools

    import numpy as np
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    names = list(mesh.axis_names)
    real = [i for i, n in enumerate(names) if ids.shape[i] > 1]
    out = {}
    for r in range(1, len(real) + 1):
        for combo in itertools.combinations(real, r):
            moved = np.moveaxis(ids, combo, range(len(combo)))
            flat = moved.reshape(
                int(np.prod([ids.shape[i] for i in combo])), -1)
            groups = frozenset(frozenset(int(x) for x in flat[:, j])
                               for j in range(flat.shape[1]))
            out[",".join(names[i] for i in combo)] = groups
    return out


def _attr_axes(groups: List[Tuple[int, ...]],
               partitions: Dict[str, frozenset]) -> str:
    """Mesh-axis label for a collective's replica groups; 'unknown' when
    no axis subset matches, '' when no mesh was given."""
    if not partitions or not groups:
        return ""
    gset = frozenset(frozenset(g) for g in groups)
    for label, part in partitions.items():
        if gset == part:
            return label
    # subset match: op groups over FEWER devices than the mesh (e.g. a
    # program compiled over a mesh slice) — report containment
    for label, part in partitions.items():
        if all(any(g <= p for p in part) for g in gset):
            return label + "?"
    return "unknown"


@dataclasses.dataclass
class CollectiveOp:
    """One collective instruction of the compiled (per-device) program."""
    kind: str                    # all-gather / all-reduce / ...
    result_bytes: int            # bytes of the instruction's result shape
    shapes: List[Tuple[str, Tuple[int, ...]]]
    group_size: int              # participants per replica group
    n_groups: int
    axes: str                    # mesh-axis label ("data", "data,expert",
    #                              "unknown", "" when no mesh given)
    channel_id: Optional[int] = None
    dimension: Optional[int] = None

    @property
    def wire_bytes(self) -> int:
        """Estimated bytes ONE participant moves over the interconnect
        (ring algorithm accounting; exact for the standard algorithms):

        * all-gather: receives (g-1)/g of the gathered result;
        * reduce-scatter: result is the 1/g shard — sends/combines
          (g-1) x result;
        * all-reduce: reduce-scatter + all-gather = 2(g-1)/g x result;
        * all-to-all / collective-broadcast: (g-1)/g of the result;
        * collective-permute: the full result crosses a link.
        """
        g = max(self.group_size, 1)
        r = self.result_bytes
        if self.kind in ("all-gather", "all-to-all", "collective-broadcast",
                         "ragged-all-to-all"):
            return r * (g - 1) // g
        if self.kind == "reduce-scatter":
            return r * (g - 1)
        if self.kind == "all-reduce":
            return 2 * r * (g - 1) // g
        return r                               # collective-permute

    def to_dict(self):
        return {"kind": self.kind, "result_bytes": self.result_bytes,
                "wire_bytes": self.wire_bytes,
                "shapes": [f"{d}[{','.join(map(str, s))}]"
                           for d, s in self.shapes],
                "group_size": self.group_size, "n_groups": self.n_groups,
                "axes": self.axes, "channel_id": self.channel_id}


def parse_hlo_collectives(hlo_text: str, mesh=None) -> List[CollectiveOp]:
    """Extract every collective op (with bytes + mesh-axis attribution)
    from post-optimization HLO text. ``-done`` halves of async pairs are
    skipped — the ``-start`` carries the transfer."""
    partitions = _mesh_axis_partitions(mesh) if mesh is not None else {}
    mesh_size = int(getattr(mesh, "size", 0) or 0)
    ops = []
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_LINE_RE.match(line)
        if not m or m.group(3) == "-done":
            continue
        shape_str, kind = m.group(1), m.group(2)
        elements = _shape_elements(shape_str)
        shapes = [(d, s) for d, s, _ in elements]
        if m.group(3) == "-start" and len(elements) > 1:
            result_bytes = _async_result_bytes(kind, elements)
        else:
            result_bytes = sum(b for _, _, b in elements)
        if kind == "collective-permute":
            pairs = []
            pm = _SOURCE_TARGET_RE.search(line)
            if pm:
                pairs = [tuple(int(x) for x in p.strip("{} ").split(","))
                         for p in pm.group(1).replace("},{", "|").split("|")
                         if p.strip("{} ")]
            groups, group_size = pairs, 2
        else:
            gm = _REPLICA_GROUPS_RE.search(line)
            groups = parse_replica_groups(gm.group(1)) if gm else []
            if not groups and mesh_size:
                # replica_groups={} is XLA's "every participant in one
                # group" — without the expansion the op would carry
                # group_size 1 / wire_bytes 0 and vanish from the
                # comm accounting
                groups = [tuple(range(mesh_size))]
            group_size = len(groups[0]) if groups else 1
        cm = _CHANNEL_RE.search(line)
        dm = _DIM_ATTR_RE.search(line)
        ops.append(CollectiveOp(
            kind=kind, result_bytes=result_bytes, shapes=shapes,
            group_size=group_size, n_groups=len(groups),
            axes=_attr_axes(groups, partitions),
            channel_id=int(cm.group(1)) if cm else None,
            dimension=int(dm.group(1)) if dm else None))
    return ops


@dataclasses.dataclass
class HloCensus:
    """The full static census of one compiled program.

    ``flops`` / ``bytes_accessed`` are the compiler's own cost analysis of
    the PER-DEVICE program (an SPMD module is the single-device slice, so
    these are per-chip numbers — multiply by device count for the global
    figure). ``hbm_watermark_bytes`` = arguments + outputs - aliased +
    temps: what must be simultaneously live in device memory, before any
    scheduler refinement."""
    flops: float = 0.0
    transcendentals: float = 0.0
    bytes_accessed: float = 0.0
    argument_bytes: int = 0
    output_bytes: int = 0
    alias_bytes: int = 0
    temp_bytes: int = 0
    generated_code_bytes: int = 0
    collectives: List[CollectiveOp] = dataclasses.field(default_factory=list)
    n_devices: int = 1

    @property
    def hbm_watermark_bytes(self) -> int:
        return (self.argument_bytes + self.output_bytes
                - self.alias_bytes + self.temp_bytes)

    @property
    def collective_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for op in self.collectives:
            out[op.kind] = out.get(op.kind, 0) + 1
        return out

    @property
    def collective_result_bytes(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for op in self.collectives:
            out[op.kind] = out.get(op.kind, 0) + op.result_bytes
        return out

    @property
    def collective_wire_bytes(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for op in self.collectives:
            out[op.kind] = out.get(op.kind, 0) + op.wire_bytes
        return out

    @property
    def collective_bytes_by_axis(self) -> Dict[str, int]:
        """Per-participant wire bytes, keyed by mesh-axis label."""
        out: Dict[str, int] = {}
        for op in self.collectives:
            key = op.axes or "unattributed"
            out[key] = out.get(key, 0) + op.wire_bytes
        return out

    @property
    def total_wire_bytes(self) -> int:
        return sum(op.wire_bytes for op in self.collectives)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "flops": self.flops,
            "transcendentals": self.transcendentals,
            "bytes_accessed": self.bytes_accessed,
            "memory": {
                "argument_bytes": self.argument_bytes,
                "output_bytes": self.output_bytes,
                "alias_bytes": self.alias_bytes,
                "temp_bytes": self.temp_bytes,
                "generated_code_bytes": self.generated_code_bytes,
                "hbm_watermark_bytes": self.hbm_watermark_bytes,
            },
            "n_devices": self.n_devices,
            "collectives": {
                "counts": self.collective_counts,
                "result_bytes": self.collective_result_bytes,
                "wire_bytes": self.collective_wire_bytes,
                "bytes_by_axis": self.collective_bytes_by_axis,
                "total_wire_bytes": self.total_wire_bytes,
                "ops": [op.to_dict() for op in self.collectives],
            },
        }


def census_compiled(compiled, mesh=None) -> HloCensus:
    """Census a ``jax.stages.Compiled`` (or anything exposing
    ``cost_analysis`` / ``memory_analysis`` / ``as_text``). Pure reading:
    never triggers tracing or compilation. Each analysis is best-effort —
    a backend refusing one zeroes that section instead of failing the
    census."""
    from deepspeed_tpu.utils.logging import logger
    census = HloCensus()
    try:
        costs = dict(compiled.cost_analysis() or {})
        census.flops = float(costs.get("flops", 0.0))
        census.transcendentals = float(costs.get("transcendentals", 0.0))
        census.bytes_accessed = float(costs.get("bytes accessed", 0.0))
    except Exception as e:
        logger.warning("[hlo-census] cost_analysis unavailable (%s); "
                       "flops/bytes report 0", e)
    try:
        ma = compiled.memory_analysis()
        census.argument_bytes = int(ma.argument_size_in_bytes)
        census.output_bytes = int(ma.output_size_in_bytes)
        census.alias_bytes = int(ma.alias_size_in_bytes)
        census.temp_bytes = int(ma.temp_size_in_bytes)
        census.generated_code_bytes = int(
            getattr(ma, "generated_code_size_in_bytes", 0))
    except Exception as e:
        logger.warning("[hlo-census] memory_analysis unavailable (%s); "
                       "watermark reports 0", e)
    try:
        census.collectives = parse_hlo_collectives(compiled.as_text(),
                                                   mesh=mesh)
    except Exception as e:
        logger.warning("[hlo-census] HLO text parse failed (%s); "
                       "collectives report empty", e)
    if mesh is not None:
        census.n_devices = getattr(mesh, "size", 1)
    return census


def collective_schedule_positions(hlo_text: str) -> List[Dict[str, Any]]:
    """Normalized instruction positions of the collectives inside the
    ENTRY computation — the tail-clustering evidence for comm overlap.

    Each collective (``-done`` halves skipped, as everywhere in this
    module) is reported as ``{"kind", "pos"}`` with ``pos`` = its index
    over the entry computation's instruction count, in [0, 1]. A program
    whose gradient reductions are serialized behind the whole backward
    shows them clustered near 1.0; per-bucket reductions issued as the
    backward produces each bucket spread across the stream. The dump
    order is the dependency/schedule order XLA prints post-optimization
    — structural evidence, not a measured timeline (the measured half is
    the off/on step time next to it in ``OVERLAP_BENCH.json``)."""
    lines = hlo_text.splitlines()
    entry, depth = [], 0
    in_entry = False
    for line in lines:
        if not in_entry and line.lstrip().startswith("ENTRY "):
            in_entry = True
            depth = line.count("{") - line.count("}")
            continue
        if not in_entry:
            continue
        depth += line.count("{") - line.count("}")
        if "=" in line:
            entry.append(line)
        if depth <= 0:
            break
    total = len(entry)
    out: List[Dict[str, Any]] = []
    for i, line in enumerate(entry):
        m = _COLLECTIVE_LINE_RE.match(line)
        if not m or m.group(3) == "-done":
            continue
        out.append({"kind": m.group(2) + (m.group(3) or ""),
                    "pos": round(i / max(total - 1, 1), 4)})
    return out


def census_fn(fn, *args, mesh=None, static_argnums=()) -> HloCensus:
    """Compile-from-scratch fallback: jit + lower + compile ``fn(*args)``
    and census the artifact. This PAYS ONE XLA COMPILE — callers holding
    an engine should go through ``engine.get_cost_census()``, which reads
    the engine's own compiled step program instead."""
    import jax
    compiled = jax.jit(fn, static_argnums=static_argnums).lower(
        *args).compile()
    return census_compiled(compiled, mesh=mesh)
