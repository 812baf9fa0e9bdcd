"""Named spans at the campaign path's layer boundaries.

Each span is a ``jax.profiler.TraceAnnotation`` named ``repro.<layer>``,
so a profile captured around a campaign holds the host's layers on the
same clock as the device's operations.  Counts ride along as annotation
arguments (``bytes=...``) and come back as the event's stats.  With no
profile active a span costs about a microsecond and records nothing.
"""

from __future__ import annotations

import jax
import numpy as np

PREFIX = "repro."
#: every span the program opens, without the prefix
NAMES = ("task", "read", "fetch", "decode", "band_math", "h2d", "dispatch",
         "device_wait", "cc_merge", "write", "pyramid", "polygonize")


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    """The span ``repro.<name>``, with ``counts`` as its arguments."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **counts)


def to_device(*arrays: np.ndarray, shardings=None):
    """Send host arrays to the device, in order, and wait until they are
    there: one ``h2d`` span counting their bytes.  With ``shardings`` (one
    per array, e.g. a ``NamedSharding`` over a mesh) each array is split
    over the devices of its sharding."""
    shardings = shardings or (None,) * len(arrays)
    with span("h2d", bytes=sum(int(a.nbytes) for a in arrays)):
        out = tuple(jax.device_put(a, s) for a, s in zip(arrays, shardings))
        jax.block_until_ready(out)
    return out


def to_host(x: jax.Array) -> np.ndarray:
    """A device result on the host: the wait for it is a ``device_wait``
    span, the copy that follows is not."""
    with span("device_wait"):
        jax.block_until_ready(x)
    return np.asarray(x)
