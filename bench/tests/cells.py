"""The benchmark's cells cut to a size the CPU tests run."""
from bench import run
from bench.models import family


def tiny(cell, per_worker=64):
    """Cell ``cell`` at 4 workers of ``per_worker`` samples, 6 rounds, 300
    test samples, and its family's ``tiny`` model."""
    wl, cfg = run.load_cell(cell)
    cfg = dict(cfg, num_workers=4, train_per_worker=per_worker, test_size=300, rounds=6)
    cfg["model"] = family(cfg["model"]["kind"]).tiny(cfg["model"])
    return wl, cfg
