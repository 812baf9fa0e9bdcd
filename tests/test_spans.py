"""The program's layer spans (``repro.core.spans``): their names, and that
they cost the program nothing but the span when no profile is active."""

import re
from pathlib import Path

import numpy as np

from repro.core import spans

SRC = Path(spans.__file__).resolve().parents[1]


def test_every_span_opened_is_named_and_every_name_opened():
    opened = set()
    for path in SRC.rglob("*.py"):
        opened |= set(re.findall(r'\bspan\("([a-z_0-9]+)"',
                                 path.read_text()))
    assert opened == set(spans.NAMES)
    assert len(spans.NAMES) == len(set(spans.NAMES))


def test_span_works_with_no_profile_active():
    with spans.span("task", task="in/t0", worker="node0"):
        with spans.span("h2d", bytes=2**40):
            x = 1 + 1
    assert x == 2
    try:
        with spans.span("read"):
            raise KeyError("passes through")
    except KeyError as e:
        assert e.args == ("passes through",)


def test_to_device_and_back_keep_values_and_order():
    a = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    b = a[..., 1]  # a strided view, as the composite sends
    c = np.array([True, False])
    sent = spans.to_device(a, b, c)
    assert [x.shape for x in sent] == [(2, 3, 4), (2, 3), (2,)]
    for host, dev in zip((a, b, c), sent):
        back = spans.to_host(dev)
        assert back.dtype == host.dtype and np.array_equal(back, host)
