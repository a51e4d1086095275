"""One module per set of layer equations; ``chipbench/arch.py`` says what a
module provides and finds it by the configuration's ``"arch"`` key."""
