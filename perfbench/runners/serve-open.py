"""Traffic kind ``serve-open``: see ``perfbench/serving.py``."""
from perfbench.serving import run  # noqa: F401
