"""Tests of the yardstick itself; run by hand (``python -m pytest
benchmark/tests -q``, about three minutes), not part of the repo's tier-1."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
