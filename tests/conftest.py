import os
import sys

# src-layout import path (tests run as PYTHONPATH=src pytest tests/)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

jax.config.update("jax_enable_x64", False)
