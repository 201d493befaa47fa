"""The chip benchmark of the batched swarm: cells, traffic, reference
check and trace reduction.  Entry point: ``python3 bench/run.py``."""
