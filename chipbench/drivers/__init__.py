"""Path drivers: one per way of driving the system (see harness.py)."""
