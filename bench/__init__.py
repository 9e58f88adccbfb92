"""On-chip benchmark of the AgentRM serving path (see ``bench/run.py``)."""
