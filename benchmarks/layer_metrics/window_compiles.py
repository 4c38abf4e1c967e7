"""Programs compiled (compile-cache misses) inside the measured window.
Must be 0 in every run after a cell's first in a checkout."""


def read(obs):
    return obs["window_compiles"]
