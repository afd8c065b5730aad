"""The deployment of a configuration, as rules and as the program's
ingest plane.

``rules`` is the yardstick's own statement of the rule set (the
reference reads it); ``processor`` builds the system under test from it
through the program's public constructors.
"""
from __future__ import annotations

import numpy as np

from chipbench.gen import planted_terms


def rules(config: dict) -> list:
    """[(rule_id, name, term, fields)]: the planted terms in the order
    the generator plants them, filler literals that match nothing, and
    the dense rule last, ``config["rules"]`` in all."""
    out = [(i, term, term, (field,))
           for i, (term, field, _) in enumerate(planted_terms(config))]
    dense = config["dense_rule"]
    for i in range(len(out), config["rules"] - 1):
        out.append((i, f"filler{i}", f"QQfiller{i:04d}qq", ("*",)))
    out.append((config["rules"] - 1, dense["name"], dense["term"],
                tuple(dense["fields"])))
    return out


def words(config: dict) -> int:
    return (config["rules"] + 31) // 32


def program_ruleset(config: dict):
    from repro.core.patterns import Rule, RuleSet
    return RuleSet(tuple(Rule(rid, name, term, fields=fields)
                         for rid, name, term, fields in rules(config)))


def processor(config: dict):
    """(ruleset, bundle, StreamProcessor) of the program, as the ingest
    launcher builds them."""
    from repro.core.matcher import compile_bundle
    from repro.core.stream_processor import StreamProcessor
    ruleset = program_ruleset(config)
    fields = tuple(f"content{i}" for i in
                   range(1, config["content_fields"] + 1))
    bundle = compile_bundle(ruleset, fields)
    return ruleset, bundle, StreamProcessor(
        bundle, mode=config["mode"], backend=config["match_backend"])


class Source:
    """The pipeline's record source over columns held in memory.
    Record ``i`` is row ``i % rows`` of the columns, stamped with
    timestamp ``i * 1000``, so a pool of rows can feed a stream of any
    length."""

    def __init__(self, cols: dict):
        from repro.core.records import RecordBatch
        self._batch = RecordBatch
        self.cols = cols
        self.rows = len(cols["timestamp"])

    def batch(self, start: int, n: int):
        idx = np.arange(start, start + n) % self.rows
        cols = {k: v[idx] for k, v in self.cols.items()}
        cols["timestamp"] = np.arange(start, start + n, dtype=np.int64) * 1000
        return self._batch(cols)
