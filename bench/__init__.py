"""On-chip benchmark of the LCCS-LSH serving path (see `bench/run.py`)."""
