"""Closed-loop ingest: a seeded pool of records fed to the program's
``IngestPipeline`` as fast as it takes them, for the whole window.

The pool is cycled with advancing timestamps, so no program is fast
enough to run out of it.  The window feeds one segment's worth of
records per ``IngestPipeline.run`` call and stops at the first segment
boundary past ``--seconds``; ``ingest_rps`` is the records acknowledged
(journaled to the WAL and sealed into the store) over the time taken.

Checks after the window, on the files the store left on disk: every
acknowledged record is in exactly one sealed segment, and every sealed
row's rule bitmap equals the reference's for its text.
"""
from __future__ import annotations

import json
import shutil
import time
from pathlib import Path

import numpy as np

from chipbench import world
from chipbench.gen import Generator
from chipbench.reference import Corpus


def setup(run):
    from repro.core.query.store import SegmentStore
    from repro.data.pipeline import IngestPipeline
    cfg, traffic = run.config, run.traffic
    pool = Generator(cfg, run.seed).records(0, traffic["pool_records"])
    _, bundle, proc = world.processor(cfg)
    source = world.Source(pool)
    # warm every shape the window uses: full batches and the short batch
    # that ends a segment, the seal, the spill and the WAL
    warm = run.work / f"{run.cell['name']}-warm"
    shutil.rmtree(warm, ignore_errors=True)
    store = SegmentStore(segment_size=cfg["segment_size"], root=warm,
                         index_fields=tuple(cfg["text_index_fields"]))
    IngestPipeline(source, store, proc, wal=cfg["wal"]).run(
        batch_size=cfg["batch_size"], limit=traffic["warmup_records"])
    del store
    shutil.rmtree(warm)
    root = run.work / f"{run.cell['name']}-store"
    shutil.rmtree(root, ignore_errors=True)
    engines = list(bundle.engines.values())
    return {"run": run, "pool": pool, "proc": proc, "source": source,
            "root": root, "acked": 0, "elapsed": 0.0,
            # the table shapes the program chose, for the roofline
            "shapes": {"width": cfg["text_width"], "words": bundle.words,
                       "engines": len(engines),
                       "states": max(e.bucket for e in engines),
                       "classes": max(e.n_classes for e in engines),
                       "block": proc.block_n}}


def measure(state, window) -> None:
    from repro.core.query.store import SegmentStore
    from repro.data.pipeline import IngestPipeline
    run = state["run"]
    cfg = run.config
    seg = cfg["segment_size"]
    store = SegmentStore(segment_size=seg, root=state["root"],
                         index_fields=tuple(cfg["text_index_fields"]))
    pipe = IngestPipeline(state["source"], store, state["proc"],
                          wal=cfg["wal"])
    window.shapes = state["shapes"]
    window.begin()
    t0 = time.perf_counter()
    acked = pipe.recover()
    while time.perf_counter() - t0 < run.seconds:
        pipe.run(batch_size=cfg["batch_size"], start=acked,
                 limit=acked + seg)
        acked += seg
    t1 = time.perf_counter()
    window.end(t0, t1)
    state["acked"], state["elapsed"] = acked, t1 - t0
    state["quarantined"] = pipe.quarantined


def report(state, window):
    run = state["run"]
    fed, lost = state["acked"], state["quarantined"]
    run.note(f"ingest fed {fed} records in {state['elapsed']} s, "
             f"quarantined {lost}")
    return ({"ingest_rps": (fed - lost) / state["elapsed"]}, fed, lost)


def _sealed(root: Path, enrich: str) -> tuple:
    """(record indices, bitmaps) of every segment the manifest lists."""
    path = root / "manifest.json"
    names = (json.loads(path.read_text())["segments"].values()
             if path.exists() else ())      # no segment ever committed
    ts, bm = [], []
    for name in names:
        ts.append(np.load(root / name / "timestamp.npy"))
        bm.append(np.load(root / name / f"{enrich}.npy"))
    if not ts:
        return np.zeros(0, np.int64), None
    return np.concatenate(ts) // 1000, np.concatenate(bm)


def verify(state, control: bool) -> list:
    from repro.core.stream_processor import ENRICH_COLUMN
    run = state["run"]
    cfg = run.config
    acked = state["acked"]
    ids, bm = _sealed(state["root"], ENRICH_COLUMN)
    w = world.words(cfg)
    expect = Corpus(state["pool"]).rule_bitmaps(
        [(r, t, f) for r, _, t, f in world.rules(cfg)], w)
    if control:
        # the control: the reference in the program's place, with the
        # last batch of the window lost, as an ingest without its WAL
        # would lose it in a crash before the seal
        ids = np.arange(acked - cfg["batch_size"])
        bm = expect[ids % len(expect)]
    uniq, n = np.unique(ids, return_counts=True)
    missing = acked - int(np.isin(np.arange(acked), uniq).sum())
    dup = int((n - 1).sum())
    extra = int((uniq >= acked).sum())
    wrong = 0
    if bm is not None and len(ids):
        want = expect[ids % len(expect)]
        k = min(bm.shape[1], w)
        wrong = int(((bm[:, :k] != want[:, :k]).any(axis=1)
                     | (bm[:, k:] != 0).any(axis=1)).sum())
    run.note(f"ingest sealed rows {len(ids)} of {acked} acknowledged")
    return [("rows_missing", missing, 0), ("rows_duplicated", dup, 0),
            ("rows_unacknowledged", extra, 0),
            ("bitmap_rows_wrong", wrong, 0)]


def close(state) -> None:
    shutil.rmtree(state["root"], ignore_errors=True)

