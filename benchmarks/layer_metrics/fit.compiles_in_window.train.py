"""Backend compiles inside the measured window (``xla_compile_count()``
after minus before). Must be 0; ``correct`` holds it to that."""


def read(obs):
    return obs.get("compiles_in_window") if obs.get("kind") == "fit_cycle" else None
