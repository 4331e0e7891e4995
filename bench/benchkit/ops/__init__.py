"""One module per operation a traffic mix can name (``"op"``).

Each holds the front-door call, the counts per call (keys, bytes at the
API), a plain numpy reference that imports nothing of ``repro``, the
control (that reference one precision below), and the comparison with a
limit per number compared."""
