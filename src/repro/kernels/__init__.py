"""The device stages of the write and read graphs, as jnp programs.

ops.py holds the stages' jitted entry points: the write side's hash,
confirmed match extension and byte emission, the read side's
`decode_gather`, in-graph planner (`plan_speculative`, `plan_decode`) and
`crc32_bytes`, the CRC-32 as GF(2) matmuls that keeps verified device
restores free of content fetches.  ref.py holds their elementwise jnp
bodies.  What runs on the chip is exactly this code; the tests hold it to
independent host oracles.
"""
