"""The same quantity as ``rl_tokens_per_s``, for the cells on a mesh."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from rl_tokens_per_s import read  # noqa: E402,F401
