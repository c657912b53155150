"""Hypothesis draws the same examples on every run (``derandomize``), so
the suite passes or fails the same way each time it gates a change.
``pytest --hypothesis-profile=default`` draws fresh random examples
instead, to hunt for new failures; pin any it finds with ``@example``."""
from hypothesis import settings

settings.register_profile("deterministic", derandomize=True)
settings.load_profile("deterministic")
