"""On-chip benchmark of the DMS-SVM and LM trainers: ``python bench/run.py``."""
