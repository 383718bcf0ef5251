"""Run from the checkout root: ``PYTHONPATH=src python -m pytest bench/tests``."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
