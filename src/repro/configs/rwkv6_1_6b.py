"""rwkv6-1.6b [ssm] — "Finch": attention-free, data-dependent decay,
token-shift. head_dim 64 => 32 heads. [arXiv:2404.05892; unverified]"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="rwkv6-1.6b",
    family="ssm",
    n_layers=24,
    d_model=2048,
    n_heads=32,                # d_model / 64
    n_kv_heads=32,
    d_ff=7168,                 # channel-mix width (3.5x)
    vocab=65536,
    head_dim=64,
    rwkv=True,
    block_pattern=("rwkv",),
    norm="layernorm",
    rope_theta=10000.0,        # unused (attention-free)
    activation="relu_sq",
)


def reduced_delta_recipe(key, output_size: int = 48):
    """CPU-CI recipe: the compile-ready delta-RWKV6 serving triple.

    Returns ``(cfg, model, task)`` — a :meth:`ModelConfig.reduced` config
    with ``delta_decode=True``, an
    :func:`repro.core.deltarwkv.init_deltarwkv_model` params dict sized
    off it (compile with ``compile_delta_program(model, cell="rwkv6")``),
    and the matching ``GruTaskConfig`` for ``DeltaStreamEngine``. The
    example (``examples/lm_delta_decode.py``) and
    ``tests/test_deltarwkv.py::TestEngine::test_reduced_recipe_theta0_exact``
    (theta=0 fires nothing and prices the dense volume) both build from
    this.
    """
    from repro.core.deltarwkv import init_deltarwkv_model
    from repro.models.gru_rnn import GruTaskConfig

    cfg = CONFIG.reduced(delta_decode=True)
    model = init_deltarwkv_model(key, cfg.d_model, cfg.n_layers,
                                 output_size)
    task = GruTaskConfig(input_size=cfg.d_model, hidden_size=cfg.d_model,
                         num_layers=cfg.n_layers, output_size=output_size)
    return cfg, model, task
