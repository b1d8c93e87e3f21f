"""repro — collective data staging + many-task execution framework for TPU pods.

Reproduction and beyond-paper extension of:
  "Big Data Staging with MPI-IO for Interactive X-ray Science"
  (Wozniak, Sharma, Armstrong, Wilde, Almer, Foster).

Layers:
  repro.core         -- staging, I/O hook, leader groups, node-local cache,
                        many-task executor, dataflow futures (the paper).
  repro.kernels      -- Pallas TPU kernel for HEDM stage-1 reduction + its
                        jnp oracle.
  repro.data         -- staged input pipeline + detector-stream simulator.
  repro.checkpoint   -- sharded checkpoints w/ collective-staged restore.
  repro.runtime      -- fault tolerance, elastic rescale, restart driver.
  repro.hedm         -- the paper's application (NF/FF-HEDM stages).
"""

__version__ = "1.0.0"
