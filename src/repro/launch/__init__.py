"""Entry points: ``python -m repro.launch.serve_snn`` (the streaming SNN
serving launcher) and ``compile_cache`` (the persistent XLA compile cache
the entry points share)."""
