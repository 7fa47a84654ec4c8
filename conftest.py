"""Repo-level pytest bootstrap.

Puts ``src/`` on ``sys.path`` so a fresh checkout can run plain ``pytest``
(the tier-1 command's ``PYTHONPATH=src`` stays supported and equivalent).
"""
import os
import sys

_SRC = os.path.join(os.path.dirname(__file__), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
