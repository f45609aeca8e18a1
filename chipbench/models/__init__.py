"""Plain float32 references of the model families the benchmark runs.

Each family module gives ``spec(cfg)`` (the parameter tree, with the
seeded initialisation the system under test uses), ``layers(params,
cfg)``, ``block(kind, layer_params, x, cfg)`` and ``head(params, x,
cfg)`` (logits).  They import nothing of the system under test: weights
come from the seed through :func:`common.init_tree`, data through
:class:`common.BigramFeed`.
"""
