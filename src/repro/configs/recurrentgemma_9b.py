"""recurrentgemma-9b [hybrid] — RG-LRU + local attention, 2 recurrent : 1
attention (38 layers = 12x(rec,rec,attn) + (rec,rec)). MQA kv=1, window 2048.
[arXiv:2402.19427; unverified]"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="recurrentgemma-9b",
    family="hybrid",
    n_layers=38,
    d_model=4096,
    n_heads=16,
    n_kv_heads=1,              # MQA
    d_ff=12288,
    vocab=256000,
    head_dim=256,
    block_pattern=("rglru", "rglru", "local_attn"),
    attn_window=2048,
    rope_theta=10000.0,
    norm="rmsnorm",
    activation="gelu_tanh",
)


def reduced_delta_recipe(key, output_size: int = 48):
    """CPU-CI recipe: the compile-ready delta-RG-LRU serving triple.

    Returns ``(cfg, model, task)`` — a :meth:`ModelConfig.reduced` config
    with ``delta_decode=True``, an
    :func:`repro.core.deltarglru.init_deltarglru_model` params dict for
    the RECURRENT layers of the reduced block pattern (the delta serving
    stack holds only the RG-LRU blocks; attention layers are not delta
    targets), and the matching ``GruTaskConfig`` for
    ``DeltaStreamEngine``.
    ``tests/test_deltarglru.py::TestEngine::test_reduced_recipe_theta0_exact``
    (theta=0 fires nothing and prices the dense volume) builds from this.
    """
    from repro.core.deltarglru import init_deltarglru_model
    from repro.models.gru_rnn import GruTaskConfig

    cfg = CONFIG.reduced(delta_decode=True)
    pattern = cfg.block_pattern
    n_rec = sum(pattern[i % len(pattern)] == "rglru"
                for i in range(cfg.n_layers))
    model = init_deltarglru_model(key, cfg.d_model, n_rec, output_size)
    task = GruTaskConfig(input_size=cfg.d_model, hidden_size=cfg.d_model,
                         num_layers=n_rec, output_size=output_size)
    return cfg, model, task
