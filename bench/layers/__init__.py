"""The private layer a configuration serves, found by name.

A configuration file (``bench/configs/<name>.json``) may name its layer,
``"layer": "<name>"``; a file without the key serves ``projection``.  The
layer is the module ``bench/layers/<name>.py``, loaded by ``load``, the
way a metric is ``bench/metrics/<name>.py`` and a traffic mix
``bench/traffic/<name>.json``.  A new layer is a new file here and a
configuration that names it: the harness, ``control.py`` and the metric
readers use nothing of a layer but these functions.

``from_dict(d) -> deployment``
    The configuration file's dict made into a frozen object with at
    least ``name``, ``p`` (the field's prime), ``max_batch`` (requests
    a replay serves at most) and ``in_width`` (the width of one
    activation row the traffic draws).

``make_weights(dep, seed) -> weights``
    Every private weight of the layer, made from ``seed``.

``make_engine(dep, weights, seed, devices) -> engine``
    The system under test, on ``devices`` (Phase 2 across a mesh when
    there are more than one).  ``engine.submit(x, arrival)`` takes one
    request of ``[rows, in_width]`` activation rows and returns it;
    ``engine.run()`` serves everything submitted and returns once every
    request has decoded.  A request exposes ``.x``, ``.y`` (its decoded
    answer), ``.state`` (``repro.serve.DONE`` once decoded) and
    ``.replay`` (the session replay that served it), as
    ``repro.serve.Request`` does.

``reference(dep, weights, xs) -> [answer, ...]``
    The exact answer of every request ``x`` in ``xs``, in the plainest
    way and importing nothing of the program.

``control(dep, weights, xs) -> [answer, ...]``
    The reference put in the program's place one precision step below
    what the configuration states; its answers must fail the comparison.

``worker_products(dep, rows) -> [(m, k, n), ...]``
    The logical worker products of a request of ``rows`` rows: what the
    roofline reader unpads each launched kernel call to.

``request_flops(dep, rows) -> int``
    The plaintext FLOPs of one request of ``rows`` rows, for
    ``layer_mfu``: the work the user asks for, not the protocol's.
"""
from __future__ import annotations

import importlib.util
import os
import sys
from types import ModuleType

LAYERS_DIR = os.path.dirname(os.path.abspath(__file__))
DEFAULT = "projection"  # the layer of a configuration that names none


def known(layers_dir: str = LAYERS_DIR) -> list:
    """Names of the layers in ``layers_dir``."""
    return sorted(
        f[:-3] for f in os.listdir(layers_dir)
        if f.endswith(".py") and not f.startswith("_")
    )


def load(name: str, layers_dir: str = LAYERS_DIR) -> ModuleType:
    """The layer ``<layers_dir>/<name>.py``; an unknown name raises."""
    path = os.path.join(layers_dir, f"{name}.py")
    if name.startswith("_") or not os.path.isfile(path):
        raise ValueError(
            f"unknown layer {name!r} in {layers_dir} (known: {known(layers_dir)})"
        )
    spec = importlib.util.spec_from_file_location(f"bench_layer_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # a dataclass looks its module up there
    spec.loader.exec_module(mod)
    return mod
