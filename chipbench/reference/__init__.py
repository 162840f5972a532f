"""Plain float32 references of the configurations' training step.

One module per reference family (``zamba2``, ``mamba2``), found by the
``reference`` key of a configuration file. A family module defines
``param_shapes(c)``, ``schedule(c)``, ``BLOCKS`` and
``train_flops_per_step(c, global_batch, seq)``; ``common`` drives it.
Nothing here imports the program under test.
"""
