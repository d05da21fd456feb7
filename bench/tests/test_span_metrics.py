"""The readers of ``run_chain``'s own host spans -- retire, transfer,
concatenation and checksums -- on hand-built spans whose sums are
known, and on spans from a program that emits none of them."""
import pytest

from bench import run
from bench.spans import Span, Spans
from bench.traffic import Window

#: one call of 4 batches that brought its outputs back to the host
SPANS = [
    ("run", 0.000, 0.500),
    ("retire", 0.100, 0.140), ("wait", 0.100, 0.110),
    ("transfer", 0.110, 0.140),
    ("sync", 0.140, 0.141),
    ("retire", 0.200, 0.230), ("wait", 0.200, 0.205),
    ("transfer", 0.205, 0.230),
    ("retire", 0.300, 0.320), ("transfer", 0.300, 0.320),
    ("retire", 0.400, 0.410), ("transfer", 0.400, 0.410),
    ("concat", 0.500, 0.700),
    ("checksum", 0.700, 0.760),
    ("retire", 0.900, -1.0),                 # still open: not counted
]
#: bytes brought back, by output
OUT_BYTES = {"s12.v": 3.0e8, "s5.gy": 1.0e8, "s5.gz": 1.0e8}

EXPECTED = {
    # 40 + 30 + 20 + 10 ms of retire over 4 batches
    "retire_ms.solve": 100 / 4,
    # 500 MB over 30 + 25 + 20 + 10 ms of transfer
    "d2h_gbps.solve": 5e8 / 0.085 / 1e9,
    "host_concat_ms.solve": 200 / 4,
    "host_checksum_ms.solve": 60 / 4,
}


def spans(items=SPANS, out_bytes=OUT_BYTES):
    s = Spans(mirror=False)
    for cat, t0, t1 in items:
        s.spans.append(Span(name="x", cat=cat, t0=t0, t1=t1))
    if out_bytes:
        s.bump("host_out_bytes", dict(out_bytes))
    return s


def record(**kw):
    base = dict(
        window=Window(wall_s=1.0, calls=1, batches=4, elements=4000),
        spans=spans(), trace={}, chips=1, p=11, plan_s_per_batch=0.05,
        flops_per_element=1000, bytes_per_element=8000,
        peaks={"flops_per_s": 200e12, "bytes_per_s": 800e9})
    base.update(kw)
    return run.Record(**base)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_span_reader(name):
    assert run.load_reader(name)(record()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_span_reader_finds_nothing_without_its_spans(name):
    """A program without these spans (the parent of the change that
    added them) reads as no value, not as zero."""
    bare = spans([("run", 0.0, 0.5), ("sync", 0.1, 0.2)], out_bytes=None)
    assert run.load_reader(name)(record(spans=bare)) is None


def test_transfer_rate_needs_bytes_and_time():
    no_bytes = spans(out_bytes=None)
    assert run.load_reader("d2h_gbps.solve")(record(spans=no_bytes)) is None
    no_transfer = spans([c for c in SPANS if c[0] != "transfer"])
    assert run.load_reader("d2h_gbps.solve")(
        record(spans=no_transfer)) is None


def test_reduced_on_device_reads_retire_and_checksum_only():
    """``hbm_resident`` reduces outputs on the device: its retires are
    reduction dispatches and nothing is concatenated or transferred."""
    reduced = spans([("retire", 0.0, 0.001), ("retire", 0.1, 0.102),
                     ("checksum", 0.2, 0.2004)], out_bytes=None)
    rec = record(spans=reduced,
                 window=Window(wall_s=1.0, calls=1, batches=2,
                               elements=2000))
    assert run.load_reader("retire_ms.solve")(rec) == pytest.approx(1.5)
    assert run.load_reader("host_checksum_ms.solve")(rec) == pytest.approx(
        0.2)
    assert run.load_reader("host_concat_ms.solve")(rec) is None
    assert run.load_reader("d2h_gbps.solve")(rec) is None
