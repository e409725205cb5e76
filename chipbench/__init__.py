"""The chip benchmark of the MHLJ walk engine and fleet trainer.

Run a cell with ``python3 chipbench/run.py``; see ``chipbench/harness.py``
for how a cell's files are found by name.
"""
