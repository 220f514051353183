"""Puts the checkout's root on ``sys.path`` so that the tests import the
benchmark as the ``bench`` package."""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
