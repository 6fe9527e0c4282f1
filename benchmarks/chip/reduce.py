"""Reduction from a profiler trace to the numbers the metrics read.

`load` reads the `.xplane.pb` that `jax.profiler.trace` writes and keeps
three event lists, each as (name, start_ns, duration_ns):
  * `ops`: the device's operations (line "XLA Ops" of each TPU plane);
  * `modules`: the device's programs (line "XLA Modules");
  * `host`: the host thread that carries the harness's own annotations
    (`bench.*`), with every other event recorded on it.
Everything else works on those lists, so tests can feed recorded ones.
"""
from __future__ import annotations

import bisect
import json
import re
from dataclasses import dataclass, field
from typing import List, Tuple

Event = Tuple[str, int, int]
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW = "bench.window"


@dataclass
class Trace:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)
    host: List[Event] = field(default_factory=list)
    n_devices: int = 1

    def to_json(self) -> str:
        return json.dumps({"ops": self.ops, "modules": self.modules,
                           "host": self.host, "n_devices": self.n_devices})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls([tuple(e) for e in d["ops"]],
                   [tuple(e) for e in d["modules"]],
                   [tuple(e) for e in d["host"]], d["n_devices"])


def _events(line) -> List[Event]:
    return [(e.name, int(e.start_ns), int(e.duration_ns))
            for e in line.events]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    t = Trace(n_devices=0)
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            t.n_devices += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    t.ops += _events(line)
                elif line.name == "XLA Modules":
                    t.modules += _events(line)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                evs = _events(line)
                if any(e[0] == WINDOW for e in evs):
                    t.host = evs
    t.n_devices = max(t.n_devices, 1)
    return t


def window(t: Trace) -> Tuple[int, int]:
    """(start, end) of the harness's measured window, in trace ns."""
    for name, s, d in t.host:
        if name == WINDOW:
            return s, s + d
    raise ValueError("the trace holds no bench.window annotation")


def _clip(evs, lo, hi):
    for name, s, d in evs:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def union(evs: List[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Merged busy intervals of `evs` inside [lo, hi)."""
    out: List[List[int]] = []
    for _, a, b in sorted(_clip(evs, lo, hi), key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


CONTAINER = re.compile(r"^%?(while|conditional|call)[.\s]")


def leaves(evs: List[Event]) -> List[Event]:
    """Operations that do work themselves: a loop or call op spans its body,
    gaps inside included, so it is left out."""
    return [e for e in evs if not CONTAINER.match(e[0])]


def op_name(text: str) -> str:
    """`%copy.48 = bf16[...] copy(...)` -> `copy`."""
    head = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+.*$", "", head)


def busy_s(evs: List[Event], lo: int, hi: int, n_devices: int = 1) -> float:
    """Seconds in which some operation ran, averaged over the devices.
    (With one device's events this is the union's length.)"""
    return sum(b - a for a, b in union(leaves(evs), lo, hi)) / 1e9 \
        / n_devices


def time_s(evs: List[Event], pattern: str, lo: int, hi: int) -> float:
    """Summed duration of the events whose name matches `pattern`."""
    rx = re.compile(pattern)
    return sum(b - a for n, a, b in _clip(evs, lo, hi) if rx.search(n)) / 1e9


def programs_s(t: Trace, name: str, lo: int, hi: int,
               holding: str = None, lacking: str = None) -> float:
    """Summed device time of the programs whose name matches `name`, that
    run an operation matching `holding` and none matching `lacking`. (JAX
    names every program made from a functools.partial `jit__unknown`, so
    what a program runs tells such programs apart.)"""
    def starts(pattern):
        rx = re.compile(pattern)
        return sorted(s for n, s, _ in t.ops if rx.search(n))

    def runs(st, a, b):
        i = bisect.bisect_left(st, a)
        return i < len(st) and st[i] < b

    need = starts(holding) if holding else None
    veto = starts(lacking) if lacking else None
    rx = re.compile(name)
    return sum(b - a for n, a, b in _clip(t.modules, lo, hi)
               if rx.search(n) and (need is None or runs(need, a, b))
               and (veto is None or not runs(veto, a, b))) / 1e9


def top(evs: List[Event], lo: int, hi: int, n: int = 10):
    """The `n` operation names (instruction numbers dropped) with the most
    summed time, as [[name, seconds]]."""
    tot: dict = {}
    for name, a, b in _clip(leaves(evs), lo, hi):
        name = op_name(name)
        tot[name] = tot.get(name, 0) + (b - a)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def idle_gaps(t: Trace, lo: int, hi: int, n: int = 10):
    """The `n` longest device-idle gaps in the window, each named by the
    innermost host event on the annotated thread that covers its middle."""
    busy = union(leaves(t.ops), lo, hi)
    gaps, prev = [], lo
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if hi > prev:
        gaps.append((prev, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        mid = (a + b) // 2
        cover = [(d, name) for name, s, d in t.host if s <= mid < s + d
                 and name != WINDOW]
        out.append([min(cover)[1] if cover else "host:unannotated",
                    (b - a) / 1e9])
    return out
