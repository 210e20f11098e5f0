"""From a JAX profiler trace of the traced paths to the per-layer numbers.

The trace (``*.xplane.pb``, read with ``jax.profiler.ProfileData``) holds
one plane per TPU with the XLA ops that ran on it, and the host threads'
``TraceAnnotation`` spans (the benchmark's ``bench/fit_path`` and
``bench/warmup``, the program's mirrored ``solver/superstep``).  From it:

* the window: from the start of the first traced ``bench/fit_path`` span to
  the end of the last;
* busy time per device: the union of its op intervals in the window;
* kernel time: the device time of each Pallas call, attributed to its
  kernel module (``kernels/<module>.py``) through the source file of the
  call's instruction in the compiled program's HLO (five kernels share the
  function name ``_kernel``), else through the jitted function its
  instruction is named after, and its roofline time from the formula in
  the benchmark's ``kernels/<module>.py``; a kernel with no formula is an
  error.  On a TPU an op's event is named by its instruction's whole text
  and names no program: the program is the ``XLA Modules`` event running
  at the op's start;
* the device ops that took most time, and the idle gaps of the fullest
  device by the innermost host span they fall in.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import importlib.util
import pathlib
import re

from bench import intervals

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_SOURCE = re.compile(r"/kernels/(\w+)\.py")
_LAUNCHER = re.compile(r"jit\((\w+)\)/pallas_call")
# a TPU's ``XLA Ops`` event is named by its whole instruction text
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ")
_DEF = re.compile(r"^def (\w+)\(", re.M)
_OPERANDS = re.compile(r"custom-call\((.*?)\)(?:, |$)")
_LAYOUTS = re.compile(
    r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}")
# a Pallas call's own instruction text names its target; an op that only
# reads a call's output names the call (``..._pallas.1``) among its operands
_CUSTOM = re.compile(r"custom_call_target=\"?tpu_custom_call")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
HOST_SPANS = ("bench/", "solver/")


class NoFormula(KeyError):
    """A kernel ran that has no operations-and-bytes formula."""


@dataclasses.dataclass
class Op:
    device: int
    name: str
    start: float
    end: float
    kernel: str = None           # kernel module of a Pallas call
    operands: list = None        # [(dtype, shape), ...] of a Pallas call


@dataclasses.dataclass
class Reduced:
    window_s: float = 0.0
    busy_by_device: dict = dataclasses.field(default_factory=dict)
    kernel_s: float = 0.0
    roofline_s: float = 0.0
    op_seconds: dict = dataclasses.field(default_factory=dict)
    idle_by_span: dict = dataclasses.field(default_factory=dict)

    @property
    def busy_s(self) -> float:
        vals = list(self.busy_by_device.values())
        return sum(vals) / len(vals) if vals else 0.0

    def breakdown(self) -> dict:
        top = lambda d: [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.op_seconds),
                "idle_gaps": top(self.idle_by_span)}


def parse_operands(text: str):
    """[(dtype, shape)] of the operands of a Pallas call, from the text of
    its HLO instruction: the operand layouts a compiled module states, else
    the typed operands a trace event's instruction text shows."""
    m = _LAYOUTS.search(text) or _OPERANDS.search(text)
    if not m:
        return []
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(m.group(1))]


@functools.lru_cache(maxsize=None)
def kernel_functions() -> dict:
    """{function name: kernel module} for every function that one module of
    the program's ``kernels/`` package alone defines: a Pallas call runs
    under the name of the jitted function that launches it."""
    spec = importlib.util.find_spec("repro.kernels")
    if spec is None:
        return {}
    seen = collections.defaultdict(set)
    for loc in spec.submodule_search_locations:
        for path in pathlib.Path(loc).glob("*.py"):
            for fn in _DEF.findall(path.read_text()):
                seen[fn].add(path.stem)
    return {fn: next(iter(mods)) for fn, mods in seen.items()
            if len(mods) == 1}


def instruction(name: str) -> str:
    """The instruction name of a device op from its event's name, which on
    a TPU is the instruction's whole text (``%glm_stats_pallas.1 = ...``)."""
    m = _INSTRUCTION.match(name)
    return m.group(1) if m else name


def kernel_module(text: str, name: str = ""):
    """``kernels/<module>.py`` of a Pallas call from the text of its trace
    event and its instruction ``name``, else None: the source file where
    the text names one, else the module that defines the jitted function
    the call was launched from, named in its op name or as its instruction
    (``glm_stats_pallas.1``)."""
    m = _SOURCE.search(text)
    if m:
        return m.group(1)
    table = kernel_functions()
    launchers = _LAUNCHER.findall(text) + [re.sub(r"(\.\d+)+$", "", name)]
    return next((table[fn] for fn in launchers if fn in table), None)


def _table(text: str, title: str) -> dict:
    """{id: rest of line} of one of a compiled module's source tables."""
    start = text.find("\n" + title + "\n")
    out = {}
    if start < 0:
        return out
    for line in text[start + len(title) + 2:].splitlines():
        m = re.match(r"(\d+) (.+)$", line)
        if not m:
            break
        out[int(m.group(1))] = m.group(2)
    return out


def _field(text: str, name: str) -> int:
    return int(re.search(name + r"=(\d+)", text).group(1))


def hlo_kernels(text: str) -> dict:
    """{(program, instruction name): (kernel module, operands)} of every
    Pallas call in the text of a compiled HLO module: the program is the
    module's name, the kernel module the ``kernels/`` file of the call's
    innermost stack frame there, else the source file of its metadata, and
    the operands are the call's operand layouts."""
    program = re.match(r"HloModule ([\w.\-]+)", text)
    program = program.group(1) if program else ""
    files = {k: v.strip('"') for k, v in _table(text, "FileNames").items()}
    locs = {k: _field(v, "file_name_id")
            for k, v in _table(text, "FileLocations").items()}
    frames = {k: (_field(v, "file_location_id"), _field(v, "parent_frame_id"))
              for k, v in _table(text, "StackFrames").items()}
    out = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = instruction(line)
        m = re.search(r"stack_frame_id=(\d+)", line)
        frame, module, seen = int(m.group(1)) if m else None, None, set()
        while frame in frames and frame not in seen:
            seen.add(frame)
            loc, parent = frames[frame]
            path = files.get(locs.get(loc), "")
            if "/kernels/" in path:
                module = pathlib.Path(path).stem
                break
            frame = parent
        out[program, name] = (module or kernel_module(line, name),
                              parse_operands(line))
    return out


def load_events(trace_dir, hlo=None):
    """(device ops, host spans) of the one ``*.xplane.pb`` under
    ``trace_dir``; times in seconds on the trace's clock.  ``hlo`` is
    ``hlo_kernels`` of the compiled programs that ran: a Pallas call is
    attributed through its instruction there (in the program its event's
    ``hlo_module`` names), else through its event's own text, and one that
    neither attributes is an error."""
    from jax.profiler import ProfileData
    paths = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    data = ProfileData.from_file(str(paths[-1]))
    hlo = hlo or {}
    known = {}
    ops, spans = [], []
    for plane in data.planes:
        dev = _DEVICE_PLANE.match(plane.name)
        lines = list(plane.lines)
        modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for line in lines if dev and line.name == "XLA Modules"
                         for ev in line.events)
        for line in lines:
            if dev and line.name == "XLA Ops":
                for ev in line.events:
                    program = str(dict(ev.stats).get("hlo_module", "")) or \
                        _program_at(modules, ev.start_ns)
                    name = instruction(ev.name)
                    text = program + " " + ev.name + " " + " ".join(
                        str(v)[:4000] for _, v in ev.stats)
                    if text not in known:
                        known[text] = _attribute(name, text,
                                                 _in_program(hlo, program,
                                                             name))
                    op = Op(int(dev.group(1)), name,
                            ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9)
                    op.kernel, op.operands = known[text]
                    ops.append(op)
            elif not dev and plane.name.startswith("/host"):
                for ev in line.events:
                    if ev.name.startswith(HOST_SPANS):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    return ops, spans


def _program_at(modules: list, t_ns) -> str:
    """Name of the program (``XLA Modules`` event) running at ``t_ns``."""
    i = bisect.bisect_right(modules, (t_ns, float("inf"), "")) - 1
    return modules[i][2] if i >= 0 and modules[i][1] >= t_ns else ""


def _in_program(hlo: dict, program: str, name: str):
    """The entry of instruction ``name`` in ``hlo``: the one of the program
    whose name the event's ``program`` starts with, else the only one of
    that name, else None (instruction names are unique only within one
    program)."""
    found = [v for (prog, n), v in hlo.items() if n == name]
    mine = [v for (prog, n), v in hlo.items()
            if n == name and prog and program.startswith(prog)]
    if mine:
        return mine[0]
    return found[0] if len(found) == 1 else None


def _attribute(name: str, text: str, entry):
    """(kernel module, operands) of a device op, (None, None) if it is no
    Pallas call; ``entry`` is its instruction's entry in ``hlo_kernels``."""
    if entry is None and not _CUSTOM.search(text):
        return None, None
    module, operands = entry or (None, [])
    module = module or kernel_module(text, name)
    if module is None:
        raise NoFormula(f"Pallas call {name!r} is attributed to no kernel "
                        f"module")
    return module, operands or parse_operands(text)


def reduce_events(ops, spans, reg, *, counters=None, peaks=None,
                  window=None):
    """The per-layer numbers from device ops and host spans."""
    counters = counters or {}
    paths = [(s, e) for n, s, e in spans if n == "bench/fit_path"]
    if window is None:
        if not paths:
            return Reduced()
        window = (min(s for s, _ in paths), max(e for _, e in paths))
    lo, hi = window
    out = Reduced(window_s=hi - lo)
    steps = counters.get("supersteps", 0)
    ctx = {"live_tiles": counters.get("sweep_tile_launches", 0) / steps
           if steps else 1.0}
    by_dev = collections.defaultdict(list)
    for op in ops:
        if op.end > lo and op.start < hi:
            by_dev[op.device].append(op)
    for dev, dops in sorted(by_dev.items()):
        ivs = [(max(o.start, lo), min(o.end, hi)) for o in dops]
        out.busy_by_device[dev] = intervals.length(ivs)
    for dops in by_dev.values():
        for op in dops:
            dur = min(op.end, hi) - max(op.start, lo)
            label = re.sub(r"[.\d]+$", "", op.name)
            if op.kernel:
                label = f"kernel {op.kernel}/{label}"
            out.op_seconds[label] = out.op_seconds.get(label, 0.0) + dur
            if op.kernel:
                if not reg.has_module("kernels", op.kernel):
                    raise NoFormula(f"kernel module {op.kernel!r} has no "
                                    f"formula in {reg.root / 'kernels'}")
                flops, nbytes = reg.module("kernels", op.kernel).cost(
                    op.operands, ctx)
                out.kernel_s += dur
                out.roofline_s += max(flops / peaks["flops_bf16"],
                                      nbytes / peaks["hbm_bytes_per_s"])
    if by_dev:
        fullest = max(out.busy_by_device, key=out.busy_by_device.get)
        busy = [(o.start, o.end) for o in by_dev[fullest]]
        nested = sorted(spans, key=lambda s: s[2] - s[1])
        for g0, g1 in intervals.gaps(busy, lo, hi):
            mid = 0.5 * (g0 + g1)
            label = next((n for n, s, e in nested if s <= mid <= e),
                         "host: outside every span")
            out.idle_by_span[label] = out.idle_by_span.get(label, 0.0) + \
                (g1 - g0)
    return out


def reduce_dir(trace_dir, reg, *, counters=None, peaks=None, programs=()):
    """``reduce_events`` of the trace under ``trace_dir``; ``programs`` are
    the HLO texts of the compiled programs that ran in it."""
    hlo = {}
    for text in programs:
        hlo.update(hlo_kernels(text))
    ops, spans = load_events(trace_dir, hlo)
    return reduce_events(ops, spans, reg, counters=counters, peaks=peaks)
