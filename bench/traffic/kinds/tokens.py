"""Token ids: one int32 id per frame, uniform on ``[0, vocab_size)`` of
the configuration, drawn from the run's seed (a teacher-forced decode:
the ids do not depend on the logits)."""
import numpy as np


def frames(rng: np.random.Generator, n: int, config: dict,
           params: dict) -> np.ndarray:
    return rng.integers(0, config["vocab_size"], size=(n, 1),
                        dtype=np.int32)
