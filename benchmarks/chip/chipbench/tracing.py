"""Device time from the JAX profiler's trace, and where the device idled.

``load`` turns the profiler's ``.xplane.pb`` into a compact record: the
device operations (name, start, duration) of every TPU plane, the
modules they ran in, and the host clock's offset, taken from one
annotation the harness makes at a known ``perf_counter`` second.
``reduce`` computes from that record, for a window given in
``perf_counter`` seconds:

  * ``busy_s``: the union of device operation intervals, averaged over
    the chips; ``window_s`` the window's length;
  * ``module_s(prefix)``: device time of the modules whose name starts
    with ``prefix`` (one jitted function);
  * ``top_ops``: device time by ``module/op``, largest first;
  * ``idle_gaps``: the idle intervals of the first chip, summed by the
    innermost host span that was open at their midpoint.

A record can be saved and read back (``save``/``read``), which is how a
trace recorded on the chip serves the tests.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import time

import numpy as np

MARK = "chipbench/clock_mark"
SHORT_GAP_S = 50e-6


def mark() -> float:
    """Annotate the profiler's host timeline at a known perf_counter
    second, and return that second."""
    import jax
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(MARK):
        pass
    return t


def load(trace_dir: str, t_mark: float) -> dict:
    """Compact record of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    mark_ns, chips, lines = None, [], {}
    for plane in pd.planes:
        lines[plane.name] = [line.name for line in plane.lines]
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                dst = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dst is not None:
                    dst.extend((e.name, e.start_ns, e.duration_ns)
                               for e in line.events)
            chips.append({"plane": plane.name, "ops": ops,
                          "modules": modules})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARK:
                        mark_ns = e.start_ns
    if mark_ns is None:
        raise ValueError("the clock mark is missing from the host trace")
    return {"offset_ns": mark_ns - t_mark * 1e9, "chips": chips,
            "lines": lines}


def save(record: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(record, f)


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(iv: np.ndarray) -> np.ndarray:
    """Sorted disjoint intervals covering the (start, end) rows of iv."""
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def _clip(events, lo: float, hi: float) -> np.ndarray:
    """(start, end) seconds of events, clipped to [lo, hi]."""
    iv = np.asarray([(s, s + d) for _, s, d in events],
                    np.float64).reshape(-1, 2) / 1e9
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


class Reduced:
    """The record of one traced window, in seconds of the profiler's
    clock (``offset`` converts from perf_counter)."""

    def __init__(self, record: dict, t0: float, t1: float):
        self.record = record
        off = record["offset_ns"] / 1e9
        self.lo, self.hi = t0 + off, t1 + off
        self.offset = off
        self.window_s = t1 - t0
        self.chips = record["chips"]
        self._busy = [_union(_clip(c["ops"], self.lo, self.hi))
                      for c in self.chips]

    @property
    def busy_s(self) -> float:
        if not self.chips:
            return 0.0
        return float(np.mean([(b[:, 1] - b[:, 0]).sum() if len(b) else 0.0
                              for b in self._busy]))

    def module_s(self, prefix: str) -> float:
        """Device seconds of modules named ``prefix...`` in the window,
        summed over chips."""
        return float(sum(
            (iv[:, 1] - iv[:, 0]).sum() for c in self.chips
            for iv in [_clip([m for m in c["modules"]
                              if m[0].startswith(prefix)],
                             self.lo, self.hi)]))

    def top_ops(self, k: int = 10) -> list:
        """[[module/op, seconds]] largest first, first chip."""
        if not self.chips:
            return []
        c = self.chips[0]
        mods = sorted((s, s + d, name) for name, s, d in c["modules"])
        starts = [m[0] for m in mods]
        total = {}
        for name, s, d in c["ops"]:
            lo, hi = max(s / 1e9, self.lo), min((s + d) / 1e9, self.hi)
            if hi <= lo:
                continue
            j = int(np.searchsorted(starts, s, side="right")) - 1
            mod = (mods[j][2] if j >= 0 and mods[j][1] >= s + d
                   else "no_module")
            # an op's name is its HLO text: keep the instruction's name
            op = name.split(" = ")[0].lstrip("%")
            key = f"{mod.split('(')[0]}/{op}"
            total[key] = total.get(key, 0.0) + (hi - lo)
        return [[n, v] for n, v in sorted(total.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, spans: list, epoch: float, k: int = 10) -> list:
        """[[host span, seconds]] of the first chip's idle time, by the
        innermost host span open at each gap's midpoint.  ``spans`` are
        Chrome trace events of the program's tracer, ``epoch`` its
        perf_counter origin."""
        if not self.chips:
            return []
        busy = self._busy[0]
        edges = np.concatenate([[self.lo], busy.ravel(), [self.hi]])
        gaps = edges.reshape(-1, 2)
        gaps = gaps[gaps[:, 1] > gaps[:, 0]]
        sp = sorted(((epoch + e["ts"] / 1e6 + self.offset,
                      epoch + (e["ts"] + e["dur"]) / 1e6 + self.offset,
                      e["name"]) for e in spans), key=lambda s: s[0])
        total = {}
        for lo, hi in gaps:
            if hi - lo < SHORT_GAP_S:
                key = "gaps_under_50_us"
            else:
                mid = (lo + hi) / 2
                open_ = [s for s in sp if s[0] <= mid < s[1]]
                key = max(open_)[2] if open_ else "no_host_span"
            total[key] = total.get(key, 0.0) + float(hi - lo)
        return [[n, v] for n, v in sorted(total.items(),
                                          key=lambda kv: -kv[1])[:k]]
