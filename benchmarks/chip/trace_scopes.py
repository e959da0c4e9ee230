"""Split a profiler trace's window by the program's own stage names
(``repro.fed.spans``).

* ``scopes``: per device, for each ``fl.*`` named scope, the union of the
  intervals of the ops whose innermost ``fl.*`` segment of ``op_name`` is
  that scope, clipped to the window. A union and not a sum: a scoped loop
  and the ops of its body share a scope and overlap in time. An op with no
  ``fl.*`` segment (such as the outer loop over client chunks) counts for
  no scope.
* ``host_spans``: the ``fl.*`` host events, clipped to the window, as
  summed seconds and a count per name.

A TPU trace names each op by its HLO instruction's text without metadata,
and its events carry no ``op_name`` (a TPU v5e trace read with
``ProfileData`` has only the stats ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier``). So the ops'
``op_name`` comes from the text of the compiled round that ran
(``Compiled.as_text()``), matched by instruction name (:func:`op_names`).
A transform wraps a scope's segment (``vmap(fl.client_sgd)``); the
segment still counts.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Tuple

import numpy as np

import trace_reduce

#: a stage's segment of an ``op_name``, bare or under a transform
SCOPE = re.compile(r"\bfl\.\w+")
#: an instruction of an HLO module's text and its metadata's op_name
HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=%]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M)


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of a compiled module's text."""
    return dict(HLO_OP_NAME.findall(hlo_text))


def op_scope(op_name: str) -> str | None:
    """The innermost ``fl.*`` scope of an ``op_name``, or None."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


def reduce(trace, names: Dict[str, str]) -> dict:
    """``{"scopes": {device: {scope: s}}, "host_spans": {name: {"s", "n"}}}``
    of a trace (a path to an ``.xplane.pb`` or a loaded ``ProfileData``),
    with ``names`` the :func:`op_names` of the modules that ran."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(trace) if isinstance(trace, str) else trace
    host: List[Tuple[str, float, float]] = []
    devices: Dict[str, list] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, ev.start_ns, ev.end_ns))
        elif trace_reduce.DEVICE_PLANE.match(plane.name):
            devices[plane.name] = [
                (op_scope(names.get(trace_reduce.op_name(ev.name), "")),
                 ev.start_ns, ev.end_ns)
                for line in plane.lines if line.name == trace_reduce.OPS_LINE
                for ev in line.events]
    wins = [(s, e) for n, s, e in host if n == trace_reduce.WINDOW]
    if not wins:
        raise ValueError(f"trace has no {trace_reduce.WINDOW!r} span")
    w0, w1 = min(s for s, _ in wins), max(e for _, e in wins)
    scopes = {}
    for dev, evs in sorted(devices.items()):
        iv = defaultdict(list)
        for sc, s, e in evs:
            s, e = max(s, w0), min(e, w1)
            if sc is not None and e > s:
                iv[sc].append((s, e))
        scopes[dev] = {
            sc: float(np.sum(np.diff(trace_reduce._union(
                np.asarray(v, np.float64)), axis=1))) * 1e-9
            for sc, v in sorted(iv.items())}
    spans: Dict[str, dict] = {}
    for name, s, e in host:
        s, e = max(s, w0), min(e, w1)
        if SCOPE.fullmatch(name) and e > s:
            d = spans.setdefault(name, {"s": 0.0, "n": 0})
            d["s"] += (e - s) * 1e-9
            d["n"] += 1
    return {"scopes": scopes, "host_spans": spans}


def scope_seconds(summary: dict, scope: str) -> float:
    """Seconds of ``scope`` in the window, averaged over the traced
    devices."""
    devs = summary["scopes"].values()
    return sum(d.get(scope, 0.0) for d in devs) / max(len(devs), 1)


def span_seconds(summary: dict, name: str) -> float:
    """Summed seconds of the host span ``name`` in the window."""
    return summary["host_spans"].get(name, {}).get("s", 0.0)
