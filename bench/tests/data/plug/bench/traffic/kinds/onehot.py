"""One-hot frames over a small alphabet, the width of the family's frame:
each symbol, drawn from the seed, is held for a run of ``run_frames``
frames, so a frame changes only where a run ends."""
import numpy as np

from harness import spec


def frames(rng: np.random.Generator, n: int, config: dict, p: dict):
    (width,), dtype = spec.load_family(config).frame_spec(config)
    lo, hi = p["run_frames"]
    runs = rng.integers(lo, hi + 1, size=n)
    ids = np.repeat(rng.integers(0, width, size=n), runs)[:n]
    return np.eye(width, dtype=dtype)[ids]
