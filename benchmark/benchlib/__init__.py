"""The benchmark's own library: traffic, client, reduction, reference, emit.

Nothing here is imported by the program, and only `incontainer.py` imports
the program (it runs inside the container that holds the chip). `run.py`
puts this directory's parent on `sys.path`; no module here imports jax at
import time, because the harness process must never hold the chip.
"""
