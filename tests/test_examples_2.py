"""Example scripts, file 2 of 6 (cases and runner: _example_cases.py)."""
from _example_cases import example_test

test_example_runs = example_test(2)
