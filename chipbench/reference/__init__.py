"""Plain float32 references, one module per model family.

Each module defines the parameter layout the benchmark fills from the seed
(``shapes`` / ``init``), a per-sequence loss in straightforward
``jax.numpy`` at ``highest`` matmul precision, and nothing else.  No module
here imports the program under test.
"""
