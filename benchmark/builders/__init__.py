"""One module per kind of deployment, named by a configuration's
``builder``: ``make_data(cfg, seed, rows)`` and ``build(cfg, data)``."""
