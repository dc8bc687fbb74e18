"""Device time by kernel, and device idle time by what the worker was doing.

The names are already in the compiled program: flax wraps every module call
in a name scope, so each instruction of the optimized HLO carries an
`op_name` such as

    jit(run)/while/body/closed_call/Alphafold2/net/while/body/closed_call/
    layers/checkpoint/block/attn/triangle_multiply_outgoing/to_out/dot_general

whose components are the keys of the parameter tree. What the profiler hands
out (`jax.profiler.ProfileData`) is each device event's instruction text and
its time, not its `op_name`; the executable's own text has both, so only the
program can join them. Three parts, one clock (the profiler's):

- `KERNELS` / `kernel_of`: the vocabulary, from path components of an
  `op_name` to the kernels' names (the folder's seven, the token decoder's
  eight, `other`);
- `profile(executable, call)`: run one compiled program under the profiler
  and return its device seconds per execution by kernel;
- `reduce(profile_data, op_names)`: the reduction itself, also of a capture
  taken by someone else (`jax.profiler.start_trace` around a serving
  window): kernels where a table is given, and every device idle gap booked
  to the scheduler worker's interval (`obs/trace.py` enters a
  `jax.profiler.TraceAnnotation` for each when the tracer is on) that covers
  most of it.

Read with `jax.profiler.ProfileData` alone: no TensorFlow, no TensorBoard
plugin.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile
from typing import Callable, Dict, Optional

# Ordered: the kernels as every table prints them. `other` holds embeddings,
# the recycling embedder, heads, loss, optimizer, glue, and whatever has no
# `op_name` at all (reported apart as `unnamed_s`).
FOLD_KERNEL_NAMES = ("triangle_multiply", "triangle_attention",
                     "msa_row_attention", "msa_col_attention",
                     "outer_product_mean", "transition", "structure")
# the causal token decoder's (`model/decoder.py`): its modules' own names.
# A layer's attention takes its kind's (latent; grouped-query under the
# causal mask or a band of keys); `expert_router` holds the scores, the
# choice, the rows' indices and both row moves; `lm_head` the embedding, the
# head and the token loss
DECODER_KERNEL_NAMES = ("mla_attention", "full_attention",
                        "window_attention", "expert_router", "expert_mlp",
                        "shared_expert", "dense_mlp", "lm_head")
KERNEL_NAMES = FOLD_KERNEL_NAMES + DECODER_KERNEL_NAMES + ("other",)

# Path component (a flax module name, so a key of the parameter tree) ->
# kernel. A component may stand anywhere in the path: backward passes
# (`transpose(jvp(Alphafold2))/net/..`), remat (`checkpoint/
# rematted_computation/..`) and scan (`while/body/..`) only add components
# around it. The innermost match decides, except that everything under the
# structure module is the structure module's (its IPA blocks have transitions
# and attention of their own).
KERNELS = (
    ("structure_module", "structure"),
    ("triangle_multiply_outgoing", "triangle_multiply"),
    ("triangle_multiply_ingoing", "triangle_multiply"),
    ("triangle_attention_outgoing", "triangle_attention"),
    ("triangle_attention_ingoing", "triangle_attention"),
    ("row_attn", "msa_row_attention"),
    ("col_attn", "msa_col_attention"),
    ("outer_mean", "outer_product_mean"),
    ("ff", "transition"),
    ("msa_ff", "transition"),
) + tuple((name, name) for name in DECODER_KERNEL_NAMES) + (
    # what the expert layer does outside its named parts (the sum of the
    # cotangents of its normed input) is the routing's glue
    ("moe", "expert_router"),
)
_SUBTREE = KERNELS[0][0]
_BY_COMPONENT = dict(KERNELS)

# The name scopes the fused kernels put around their Pallas calls
# (`ops.attention.fused_attention_merged`, `ops.triangle_multiply.
# fused_triangle_multiply`, the expert layer's row moves `ops.expert_rows`,
# inside `expert_router`): a custom call whose `op_name` has one of these
# components ran a fused kernel
FUSED_SCOPES = ("fused_attention", "fused_triangle_multiply", "expert_rows")


def _parts(op_name: Optional[str]) -> list:
    """The path components of an instruction's own `op_name` (XLA joins the
    names of instructions it merged with ";": the first is its own)."""
    return op_name.split(";", 1)[0].split("/") if op_name else []


def is_fused(opcode: str, op_name: Optional[str]) -> bool:
    """Whether a device operation is a fused kernel itself (a custom call
    under one of `FUSED_SCOPES`), not the XLA formulation a differentiated
    trace runs under the same scope."""
    return opcode == "custom-call" and any(
        scope in _parts(op_name) for scope in FUSED_SCOPES)


# The component `jax.checkpoint` puts on what a backward pass makes again
REMAT_SCOPE = "rematted_computation"


def is_remat(op_name: Optional[str]) -> bool:
    """Whether a device operation is part of a forward pass run again under
    `jax.checkpoint` (`nn.remat`) for the backward pass."""
    return REMAT_SCOPE in _parts(op_name)


# The scheduler worker's intervals that tile its time (serve/scheduler.py):
# what an idle gap of the device is booked to. `fold` is left out: it is the
# parent of `dispatch` and `device_wait`, which say more.
WORKER_SPANS = ("idle", "hold", "batch_form", "shard", "compile", "dispatch",
                "device_wait", "fetch", "resolve")

_OPS_LINE = "XLA Ops"
# loops, branches and calls hold other operations: their time is their
# bodies', which the line lists as well
_CONTAINERS = ("while", "conditional", "call")
# a gap this short is the device's own turn-around between two operations,
# not the host's doing
SHORT_GAP_NS = 20_000

_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-.]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")


def kernel_of(op_name: Optional[str]) -> str:
    """The kernel an `op_name` belongs to (`other` for none or no match)."""
    parts = _parts(op_name)
    if _SUBTREE in parts:
        return _BY_COMPONENT[_SUBTREE]
    for part in reversed(parts):
        kernel = _BY_COMPONENT.get(part)
        if kernel is not None:
            return kernel
    return "other"


# components that say how the compiler got there, not where in the model
_SCAFFOLD = ("while", "body", "closed_call", "checkpoint", REMAT_SCOPE)


def op_name_tail(op_name: Optional[str], components: int = 4) -> str:
    """The last few components of an `op_name`, loop and remat scaffolding
    left out: enough to tell where an instruction came from, short enough
    for a table."""
    parts = [p for p in _parts(op_name) if p not in _SCAFFOLD]
    return "/".join(parts[-components:])


def instruction_op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} from an executable's text
    (`compiled.as_text()`), for every instruction the device can run as an
    operation of its own (those inside fused computations are left out). A
    fusion takes its root's name; where the root has none (a bitcast or copy
    the compiler put there), the nearest instruction before it that has."""
    computations: Dict[str, list] = {}   # name -> [(instr, root, op_name,
    current = None                       #           opcode, callee)]
    for line in hlo_text.splitlines():
        if current is None:
            head = _COMPUTATION.match(line)
            if head and " = " not in line.split("(", 1)[0]:
                current = computations.setdefault(head.group(1), [])
            continue
        if line.rstrip() == "}":
            current = None
            continue
        m = _INSTRUCTION.match(line) if line.startswith(" ") else None
        if not m:
            # An instruction's text may run over several lines (a Pallas
            # call of the blocked causal attention carries a JSON attribute
            # with line breaks, and its `metadata` comes after it, on a line
            # that begins "}}"): the name belongs to the instruction above.
            named = _OP_NAME.search(line)
            if named and current and current[-1][2] is None:
                current[-1] = current[-1][:2] + (named.group(1),) \
                    + current[-1][3:]
            continue
        rest = m.group(3)
        opcode = _OPCODE.search(rest)
        named = _OP_NAME.search(rest)
        callee = _CALLS.search(rest)
        current.append((m.group(2), bool(m.group(1)),
                        named.group(1) if named else None,
                        opcode.group(1) if opcode else "",
                        callee.group(1) if callee else None))

    def fused_name(computation: str, depth: int = 0) -> Optional[str]:
        body = computations.get(computation) or []
        roots = [i for i, ins in enumerate(body) if ins[1]]
        last = roots[-1] if roots else len(body) - 1
        for _, _, named, opcode, callee in reversed(body[:last + 1]):
            if named:
                return named
            if opcode == "fusion" and callee and depth < 8:
                inner = fused_name(callee, depth + 1)
                if inner:
                    return inner
        return None

    fused = {callee for body in computations.values()
             for _, _, _, opcode, callee in body
             if opcode == "fusion" and callee}
    table = {}
    for name, body in computations.items():
        if name in fused:
            continue
        for instr, _, named, opcode, callee in body:
            if not named and opcode == "fusion" and callee:
                named = fused_name(callee)
            if named:
                table[instr] = named
    return table


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _instruction_of(event_name: str):
    """(instruction name, opcode) of a device event, whose name is the
    instruction's text: "%fusion.12 = bf16[..] fusion(..), kind=kLoop"."""
    lhs, _, rhs = event_name.partition(" = ")
    opcode = _OPCODE.search(rhs)
    return lhs.strip().lstrip("%"), opcode.group(1) if opcode else ""


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _device_lines(profile_data):
    """[(plane name, its "XLA Ops" line)] of the planes that are devices."""
    out = []
    for plane in profile_data.planes:
        if not plane.name.startswith("/device:") \
                or "host" in plane.name.lower():
            continue
        for line in plane.lines:
            if line.name == _OPS_LINE:
                out.append((plane.name, line))
                break
    return sorted(out, key=lambda pl: pl[0])


def _book_gaps(gaps, annotations):
    """Seconds of idle gaps by the annotation name that covers most of each
    (summed over that name's events: a wait of a second is two hundred
    `hold`s of a poll each)."""
    annotations = sorted(annotations)
    starts = [a[0] for a in annotations]
    reach, high = [], 0          # the latest end among events up to each one
    for _, end, _ in annotations:
        high = max(high, end)
        reach.append(high)
    booked: Dict[str, float] = {}
    for gs, ge in gaps:
        if ge - gs < SHORT_GAP_NS:
            name = "between_ops"
        else:
            cover: Dict[str, float] = {}
            i = bisect.bisect_right(reach, gs)
            stop = bisect.bisect_left(starts, ge)
            for s, e, n in annotations[i:stop]:
                c = min(e, ge) - max(s, gs)
                if c > 0:
                    cover[n] = cover.get(n, 0) + c
            name = max(cover, key=cover.get) if cover else "unannotated"
        booked[name] = booked.get(name, 0.0) + (ge - gs) / 1e9
    return booked


def reduce(profile_data, op_names: Optional[Dict[str, str]] = None,
           spans=WORKER_SPANS) -> Optional[dict]:
    """What a capture says, or None where no device ran an operation.

    `profile_data`: a `jax.profiler.ProfileData`. `op_names`: the
    instruction table of the ONE program the capture ran
    (`instruction_op_names`); without it every operation is `other` and
    `unnamed`. The window is the span from the first device operation to the
    last. Returns

    - `window_s`, `busy_s` (the union of the operations' intervals), `events`;
    - `kernels`: {kernel: {"seconds", "events", "fused_s", "remat_s"}} over
      `KERNEL_NAMES`, containers left out: the kernels' seconds sum to
      `busy_s`; `fused_s` is the part of `seconds` spent in the fused
      kernels' custom calls (`is_fused`: the attention's, the triangle
      multiply's): the counter of a
      mechanism that engages when the program is traced; `remat_s` the part
      spent making a forward pass again for the backward (`is_remat`): what
      the trunk's remat policy (`model/evoformer.py`) buys back with memory;
      `remat_s`, at the top: its sum over the kernels;
      `unnamed_s`: the part of `other` that had no `op_name`; `xla_flops` /
      `xla_bytes` per kernel where the profiler's events carry XLA's own
      counts (this installation's do not);
    - `top`: the ten dearest instructions as [XLA's name, kernel, op_name
      tail, seconds];
    - every time above is the mean over the devices that ran anything (one
      chip: its own);
    - `idle`: seconds of the first device's idle gaps by the worker span
      (`spans`) that covers most of each, `between_ops` for gaps under 20
      microseconds, `unannotated` where no span covers any of it;
      `annotations`: how many events of each span the host planes hold.
    """
    op_names = op_names or {}
    devices = _device_lines(profile_data)
    kernels = {k: {"seconds": 0.0, "events": 0, "fused_s": 0.0,
                   "remat_s": 0.0} for k in KERNEL_NAMES}
    by_instr: Dict[str, float] = {}
    unnamed_ns = events = 0
    busy = []                    # each device's merged (start, end) intervals
    for _, line in devices:
        intervals, counts = [], None
        for e in line.events:
            if e.duration_ns <= 0:
                continue
            instr, opcode = _instruction_of(e.name)
            if counts is None:   # looked for once: all events have the same
                counts = {"flops", "bytes_accessed"} & {
                    k for k, _ in e.stats}
            start = e.start_ns
            intervals.append((start, start + e.duration_ns))
            if opcode in _CONTAINERS:
                continue
            named = op_names.get(instr)
            entry = kernels[kernel_of(named)]
            entry["seconds"] += e.duration_ns / 1e9
            entry["events"] += 1
            if is_fused(opcode, named):
                entry["fused_s"] += e.duration_ns / 1e9
            if is_remat(named):
                entry["remat_s"] += e.duration_ns / 1e9
            events += 1
            if named is None:
                unnamed_ns += e.duration_ns
            by_instr[instr] = by_instr.get(instr, 0.0) + e.duration_ns / 1e9
            if counts:
                stats = dict(e.stats)
                for stat, key in (("flops", "xla_flops"),
                                  ("bytes_accessed", "xla_bytes")):
                    if stat in stats:
                        entry[key] = entry.get(key, 0) + stats[stat]
        if intervals:
            busy.append(_union(intervals))
    if not busy:
        return None
    first, n = busy[0], len(busy)
    lo, hi = first[0][0], first[-1][1]
    for entry in kernels.values():
        entry["seconds"] /= n
        entry["fused_s"] /= n
        entry["remat_s"] /= n

    wanted, annotations, seen = set(spans), [], {}
    for plane in profile_data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in wanted:
                    seen[e.name] = seen.get(e.name, 0) + 1
                    annotations.append(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name))
    gaps = [(a[1], b[0]) for a, b in zip(first, first[1:])]
    top = sorted(by_instr.items(), key=lambda kv: -kv[1])[:10]
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(e - s for merged in busy
                          for s, e in merged) / n / 1e9,
            "events": events,
            "devices": [name for name, _ in devices],
            "kernels": kernels,
            "unnamed_s": unnamed_ns / n / 1e9,
            "remat_s": sum(e["remat_s"] for e in kernels.values()),
            "top": [["%" + instr, kernel_of(op_names.get(instr)),
                     op_name_tail(op_names.get(instr)), seconds / n]
                    for instr, seconds in top],
            "idle": _book_gaps(gaps, annotations),
            "annotations": seen}


def profile(executable, call: Callable[[], object],
            repeats: int = 3) -> dict:
    """Where one compiled program spends the chip at one shape.

    `executable`: a compiled executable (`jitted.lower(*args).compile()`);
    `call`: a zero-argument function that executes it once and returns only
    when the device has finished. Runs `call` once unprofiled, then `repeats`
    times under `jax.profiler` (python tracer off; the capture goes to a
    temporary directory that is removed), and returns `reduce`'s kernels
    (`seconds`, `fused_s` and `remat_s`), `unnamed_s`, `remat_s`, `busy_s`
    and `top` PER EXECUTION, with `repeats` and the `window_s` of all of them.
    Raises where the capture holds no device operation (the CPU backend has
    no device plane).
    """
    import jax

    table = instruction_op_names(executable.as_text())
    call()
    trace_dir = tempfile.mkdtemp(prefix="af2_device_profile_")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            for _ in range(repeats):
                call()
        finally:
            jax.profiler.stop_trace()
        path = find_xplane(trace_dir)
        reduced = reduce(jax.profiler.ProfileData.from_file(path), table) \
            if path else None
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if reduced is None:
        raise RuntimeError(
            "obs.device.profile: the capture holds no device operation "
            f"(backend {jax.default_backend()!r} has no device plane)")
    per = lambda x: x / repeats
    for entry in reduced["kernels"].values():
        entry["seconds"] = per(entry["seconds"])
        entry["events"] //= repeats
        for key in ("fused_s", "remat_s", "xla_flops", "xla_bytes"):
            if key in entry:
                entry[key] = per(entry[key])
    return {"repeats": repeats, "window_s": reduced["window_s"],
            "busy_s": per(reduced["busy_s"]),
            "events": reduced["events"] // repeats,
            "kernels": reduced["kernels"],
            "unnamed_s": per(reduced["unnamed_s"]),
            "remat_s": per(reduced["remat_s"]),
            "top": [row[:3] + [per(row[3])] for row in reduced["top"]]}
