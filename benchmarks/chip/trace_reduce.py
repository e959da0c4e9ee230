"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

* the traced window: the host span ``bench.window`` the harness writes;
* per device (``/device:TPU:<n>`` planes, ``XLA Ops`` line): the union of
  the intervals in which an operation ran, clipped to the window (busy),
  and the summed duration of each operation by its HLO instruction's name
  (a TPU trace names an op by the instruction's whole text,
  ``%<name> = <shape> <opcode>(...)``; the name alone is kept). A Pallas
  call is named ``<kernel>.<n>`` after its kernel, with suffixes such as
  ``.clone`` where it sits in a loop body;
* the device's idle gaps inside the window, each named by the host event
  that overlaps it most (ties go to the shorter, more specific event).

Read with ``jax.profiler.ProfileData``; nothing else is needed.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
#: host spans too broad to say what the host was doing in a gap
BROAD = {WINDOW, "bench.round"}
#: an op named by its HLO text: ``%<name> = ...``
HLO_TEXT = re.compile(r"%?([^\s=%]+) = ")


def op_name(name: str) -> str:
    """The HLO instruction's name of a trace op named by its text (or the
    name itself where the op carries only that)."""
    m = HLO_TEXT.match(name)
    return m.group(1) if m else name


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (n, 2) [start, end) intervals into disjoint sorted ones."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def reduce(trace, n_gaps: int = 10) -> dict:
    """Summary of a trace (a path to an ``.xplane.pb`` or a loaded
    ``ProfileData``): window, per-device busy seconds and op seconds, and
    the longest idle gaps with their host labels."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(trace) if isinstance(trace, str) else trace
    host: List[Tuple[str, float, float]] = []
    devices: Dict[str, list] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, ev.start_ns, ev.end_ns))
        elif DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    evs.append((op_name(ev.name), ev.start_ns, ev.end_ns))
            devices[plane.name] = evs
    wins = [(s, e) for n, s, e in host if n == WINDOW]
    if not wins:
        raise ValueError(f"trace has no {WINDOW!r} span")
    w0, w1 = min(s for s, _ in wins), max(e for _, e in wins)
    out = {"window_s": (w1 - w0) * 1e-9, "devices": {}, "gaps": []}
    for name, evs in sorted(devices.items()):
        ops: Dict[str, float] = defaultdict(float)
        iv = []
        for op, s, e in evs:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                ops[op] += (e - s) * 1e-9
                iv.append((s, e))
        busy = _union(np.asarray(iv, np.float64).reshape(-1, 2))
        out["devices"][name] = {
            "busy_s": float(np.sum(busy[:, 1] - busy[:, 0])) * 1e-9,
            "ops": dict(ops)}
        if not out["gaps"]:
            out["gaps"] = _gaps(busy, w0, w1, host, n_gaps)
    return out


def _gaps(busy: np.ndarray, w0, w1, host, n: int):
    edges = np.concatenate([[w0], busy.reshape(-1), [w1]]).reshape(-1, 2)
    gaps = sorted(((e - s, s, e) for s, e in edges if e > s), reverse=True)
    spans = [(nm, s, e) for nm, s, e in host if nm not in BROAD and e > s]
    out = []
    for length, s, e in gaps[:n]:
        best, key = "idle", (0.0, 0.0)
        for nm, hs, he in spans:
            ov = min(e, he) - max(s, hs)
            if ov > 0 and (ov, -(he - hs)) > key:
                best, key = nm, (ov, -(he - hs))
        out.append([best, length * 1e-9])
    return out


def busy_s(summary: dict) -> float:
    """Busy seconds, averaged over the traced devices."""
    devs = summary["devices"].values()
    return sum(d["busy_s"] for d in devs) / max(len(devs), 1)


def op_seconds(summary: dict, kernel: str) -> float:
    """Seconds of the ops named ``<kernel>`` or ``<kernel>.<suffix>...``
    (``.3``, ``.3.clone``), averaged over the traced devices. Only the
    instruction's name counts: another op's HLO text names the kernel
    where it reads the kernel's output."""
    own = re.compile(rf"{re.escape(kernel)}(\.[\w-]+)*")
    devs = summary["devices"].values()
    return sum(t for d in devs for op, t in d["ops"].items()
               if own.fullmatch(op)) / max(len(devs), 1)


def breakdown(summary: dict, n: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most time
    (mean over devices) and the longest idle gaps by host activity."""
    tot: Dict[str, float] = defaultdict(float)
    devs = summary["devices"].values()
    for d in devs:
        for op, t in d["ops"].items():
            tot[op] += t / len(devs)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": summary["gaps"][:n]}
