"""Model families of the benchmark, one module each: ``bench/models/<kind>.py``.

The harness finds a configuration's family by its ``model.kind`` and loads
the module by file path, as it loads ``bench/metrics/<name>.py``, so a new
family is added as new files alone.  Every family module exports:

``program_model(name, model) -> dict``
    the program's model object, as keyword arguments of the simulator's
    ``SimConfig`` (``{"cnn": CNNConfig}`` for the CNN families).  The only
    function of a family that imports the program.
``make_task(model, train_size, test_size, seed)``
    the inputs from ``seed``: an object with ``x_train``, ``y_train``,
    ``x_test`` and ``y_test``, where ``y_train`` holds one integer label per
    sample, which the Non-IID split sorts by.
``init_params(model, seed) -> {leaf: array}``
    the reference's initial model in float32.
``forward(p, model, x, precision)``
    the reference's outputs for a batch of samples.
``loss(out, y)``
    the mean training loss of ``forward``'s outputs against their labels.
``hits(out, y)``
    the samples of a batch predicted right, on the host, each counting at
    most 1; accuracy is their sum over the test set by its size.
``units(model) -> [(layer, count, [(leaf, axis), ...]), ...]``
    every prunable layer in network order, its number of units, and the
    slices a unit owns: unit ``u`` of ``layer`` is index ``u`` along
    ``axis`` of each listed leaf.  A unit's parameter cost is the size of
    its slices.
``unit_scale(p, layer)``
    the per-unit scale that ``cig_bnscalor`` ranks by; a family without
    one raises ``ValueError``.
``matmuls(model, kept) -> [(M, K, N), ...]``
    every matmul of one sample's forward pass, ``M`` rows per sample, at
    the retained unit counts ``kept`` (layer -> count; full where absent).
``tiny(model) -> model``
    the same family at a size the CPU tests run.

The reference's functions compute in the dtype of the parameters they are
given at the ``precision`` passed (plain float32 at ``HIGHEST`` for the
reference), and import nothing of the program.
"""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path
from types import ModuleType
from typing import Dict

DIR = Path(__file__).resolve().parent
CONTRACT = ("program_model", "make_task", "init_params", "forward", "loss", "hits", "units",
            "unit_scale", "matmuls", "tiny")
KIND = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
_loaded: Dict[Path, ModuleType] = {}


def family(kind: str) -> ModuleType:
    """The module of model family ``kind``, from ``DIR``; exits naming the
    file it looked for where there is none."""
    path = DIR / f"{kind}.py"
    if not (KIND.match(kind) and path.is_file()):
        raise SystemExit(f"unknown model kind {kind!r}: no file {path}")
    if path not in _loaded:
        spec = importlib.util.spec_from_file_location(
            f"bench_model_{re.sub(r'[^A-Za-z0-9_]', '_', kind)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        missing = [f for f in CONTRACT if not callable(getattr(mod, f, None))]
        if missing:
            raise SystemExit(f"model family {path} lacks {', '.join(missing)}")
        _loaded[path] = mod
    return _loaded[path]
