"""``runtime.device_decode_share`` read from synthetic ``runtime.fetch`` spans."""
import time
from types import SimpleNamespace

import pytest

from bench.harness import load_metric
from repro.obs.tracer import TRACER


def _read(decodes):
    """The metric over one window holding one fetch span per entry of
    ``decodes`` (``None``: a span without the attribute), plus one device
    fetch outside the window."""
    TRACER.clear()
    TRACER.enable()
    try:
        with TRACER.span("runtime.fetch", decode="device"):
            pass
        t0 = time.perf_counter()
        for d in decodes:
            with TRACER.span("runtime.fetch", **({} if d is None else {"decode": d})):
                pass
            with TRACER.span("runtime.device_wait", decode="device"):
                pass
        t1 = time.perf_counter()
        return load_metric("runtime.device_decode_share", "ratio").read(
            SimpleNamespace(window=SimpleNamespace(t0=t0, t1=t1))
        )
    finally:
        TRACER.disable()
        TRACER.clear()


@pytest.mark.parametrize("decodes, want", [
    (["device"] * 4, 1.0),
    (["device", "host", "device", "host", "host"], 0.4),
    (["host"] * 3, 0.0),
    ([None, None], None),
    ([], None),
])
def test_device_decode_share(decodes, want):
    assert _read(decodes) == want
