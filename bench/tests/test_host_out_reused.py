"""The reader of ``host_out_reused_pct.solve`` on records whose counters
are known.  Its expected value is kept here, beside the reader, and not
in ``test_metrics.EXPECTED``."""
import pytest

from bench import run
from bench.spans import Spans
from bench.tests.test_metrics import record

OUT = {"grad.gy": 400.0, "grad.gz": 400.0, "helmholtz.v": 400.0}


def counted(**counters):
    s = Spans(mirror=False)
    for name, values in counters.items():
        s.bump(name, values)
    return record(spans=s)


@pytest.mark.parametrize("counters,want", [
    # two calls, the second into the first's arrays
    ({"host_out_bytes": OUT, "host_out_reused_bytes": {
        "grad.gy": 200.0, "grad.gz": 200.0, "helmholtz.v": 200.0}}, 50.0),
    # a first call: the counter is there and reads nothing reused
    ({"host_out_bytes": OUT,
      "host_out_reused_bytes": dict.fromkeys(OUT, 0.0)}, 0.0),
    # a program without the counter, and outputs kept on the device
    ({"host_out_bytes": OUT}, None),
    ({}, None),
], ids=["half", "none_reused", "no_counter", "nothing_back"])
def test_host_out_reused_pct(counters, want):
    got = run.load_reader("host_out_reused_pct.solve")(counted(**counters))
    assert got == (None if want is None else pytest.approx(want))
