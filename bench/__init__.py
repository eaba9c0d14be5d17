"""On-chip benchmark of the DB-LSH vector store: ``python3 bench/run.py``."""
