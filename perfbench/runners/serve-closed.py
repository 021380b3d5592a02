"""Traffic kind ``serve-closed``: see ``perfbench/serving.py``."""
from perfbench.serving import run  # noqa: F401
