"""Shared sample scaffolding: default to the CPU backend (samples must run
anywhere; set ``JAX_PLATFORMS`` yourself, e.g. ``tpu``, to run the same code
on a chip) and put the repo on sys.path so samples run standalone:
``python samples/<name>.py``. Import this before anything that imports jax."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
