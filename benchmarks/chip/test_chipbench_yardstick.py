"""The yardstick's parts on the CPU: byte counts against hand counts,
the peaks table, the seeded schedule, the load generator's process, the
generator and the reference."""
import json
import socket
import struct
import subprocess
import sys
import threading
from pathlib import Path

import pytest

from chipbench import roofline, schedule
from chipbench.gen import Generator
from chipbench.reference import Corpus

HOME = Path(__file__).resolve().parent


def test_dfa_ref_bytes_hand_count():
    # 2 fields x 4096 rows x 512 bytes of text, a 4096 x 32-word bitmap
    # and a 4096-byte mask out, two engines of 8192 states x 40 classes
    text = 2 * 4096 * 512
    out = 4096 * 32 * 4 + 4096
    tables = 2 * (256 * 4 + 8192 * 40 * 4 + 8192 * 32 * 4)
    assert roofline.dfa_ref_bytes(fields=2, rows=4096, width=512, words=32,
                                  engines=2, states=8192,
                                  classes=40) == text + out + tables


def test_word_query_bytes_hand_count():
    rows, preds, segs = 1 << 20, 3, 32
    assert roofline.word_query_bytes(rows=rows, preds=preds,
                                     with_counts=False, segments=segs) \
        == rows * 3 * 4 + 3 * 4 + rows
    assert roofline.word_query_bytes(rows=rows, preds=preds,
                                     with_counts=True, segments=segs) \
        == rows * 3 * 4 + 3 * 4 + rows + rows * 4 + segs * 4


@pytest.mark.parametrize("n,block,want", [
    (1, 256, 256), (256, 256, 256), (257, 256, 512), (848, 256, 1024),
    (4096, 256, 4096), (950_000, 1024, 1 << 20), (1_000_000, 1024, 1 << 20),
])
def test_bucket(n, block, want):
    assert roofline.bucket(n, block) == want


def test_share_of_peak():
    peak = roofline.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    # 819 MB in 2 ms is half of 819 GB/s
    assert roofline.share_pct(819e6, 2e-3, peak) == pytest.approx(50.0)
    assert roofline.share_pct(1, 0.0, peak) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


TRAFFIC = json.loads((HOME / "traffic" / "query-dense-conj.json")
                     .read_text())


def test_schedule_is_deterministic_from_the_seed():
    a = schedule.build(TRAFFIC, 2 ** 31 + 7, 10)
    assert a == schedule.build(TRAFFIC, 2 ** 31 + 7, 10)
    b = schedule.build(TRAFFIC, 12, 10)
    assert a != b
    # seeds reorder the work and never resize it
    kinds = lambda s: sorted((q, m) for _, _, q, m in s)  # noqa: E731
    assert len(a) == len(b) == round(TRAFFIC["rate_per_s"] * 10)
    assert kinds(a) == kinds(b)
    dues = [d for d, *_ in a]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 10


def _echo_server(sock):
    """Answers each framed request with status 200 and its id as count."""
    def serve(conn):
        with conn:
            while True:
                head = conn.recv(4, socket.MSG_WAITALL)
                if len(head) < 4:
                    return
                n = struct.unpack(">I", head)[0]
                req = json.loads(conn.recv(n, socket.MSG_WAITALL))
                body = json.dumps({"id": req["id"], "status": 200,
                                   "count": req["id"]}).encode()
                conn.sendall(struct.pack(">I", len(body)) + body)
    while True:
        try:
            conn, _ = sock.accept()
        except OSError:
            return
        threading.Thread(target=serve, args=(conn,), daemon=True).start()


def test_load_generator_process_never_imports_jax(tmp_path):
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    sock.listen(16)
    threading.Thread(target=_echo_server, args=(sock,), daemon=True).start()
    reqs = [[i * 0.01, f"c{i % 3}", "q0", [["content1", "er"]], "count"]
            for i in range(20)]
    plan = {"host": "127.0.0.1", "port": sock.getsockname()[1],
            "lead_s": 0.2, "pool": 2, "timeout_s": 10, "requests": reqs}
    (tmp_path / "plan.json").write_text(json.dumps(plan))
    p = subprocess.run([sys.executable, "-m", "chipbench.loadgen",
                        str(tmp_path / "plan.json"),
                        str(tmp_path / "out.json")], cwd=HOME,
                       capture_output=True, text=True, timeout=60)
    sock.close()
    assert p.returncode == 0, p.stderr
    out = json.loads((tmp_path / "out.json").read_text())
    assert out["modules_jax"] == []
    rows = out["rows"]
    assert [r[3] for r in rows] == [200] * 20
    assert [r[4] for r in rows] == list(range(20))
    for due, sent, done, *_ in rows:
        assert due <= sent <= done


CFG = {"content_fields": 2, "text_width": 512, "vocab_seed": 7,
       "ultra_rate": 1e-3, "high_rate": 1e-2}


def test_generator_is_pure_in_seed_and_range():
    g = Generator(CFG, 2 ** 32 + 3)
    a = g.records(0, 70_000)
    b = Generator(CFG, 2 ** 32 + 3).records(65_530, 20)
    for k in b:
        assert (a[k][65_530:65_550] == b[k]).all()
    c = Generator(CFG, 4).records(0, 20)
    assert not (a["content1"][:20] == c["content1"]).all()


def test_reference_matches_python_substring_search():
    cols = Generator(CFG, 9).records(0, 3000)
    corpus = Corpus(cols)
    texts = [bytes(r) for r in cols["content1"]]
    for term in ("er", "HIGHneedle1x", "ULTRAneedle1x", "QQfiller0100qq",
                 "the", "zq"):
        want = [i for i, t in enumerate(texts) if term.encode() in t]
        assert corpus.answer([("content1", term)]).tolist() == want, term
    # planted terms land at their rate, in their field only
    high = corpus.answer([("content1", "HIGHneedle1x")])
    assert 10 <= len(high) <= 60
    assert len(corpus.answer([("content2", "HIGHneedle1x")])) == 0
