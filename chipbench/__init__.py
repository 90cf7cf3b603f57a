"""The chip benchmark of CarbonFlex: ``python3 chipbench/run.py``."""
