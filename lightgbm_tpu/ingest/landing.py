"""Pass-2 landings: where streamed, binned row chunks come to rest.

- `HostLanding`    — a preallocated host uint8/uint16 matrix (the default;
  1 byte/row/feature instead of the 8 of raw float64).
- `ShardedLanding` — per-device contiguous row blocks under a 1-D data
  mesh: each block is transferred to its device the moment the stream
  fills it and the host copy is freed, so a dataset of N x HBM rows can
  be landed on one host whose RAM never holds more than one device block
  plus one chunk. The finished `jax.Array` is sharded exactly the way
  the data/voting-parallel growers' shard_map expects (P(axis, None)),
  so training starts with zero resharding.

A sharded landing is padded by the trainer's own row plan
(`learner.schedule.plan_row_layout`), so it is byte-compatible with what
the grower would have padded itself.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from .. import log, telemetry
from ..learner.schedule import RowLayout


class HostLanding:
    """Preallocated `[n, g]` host matrix of group-bin indices."""

    def __init__(self, num_rows: int, num_groups: int, dtype):
        self.out = np.zeros((num_rows, num_groups), dtype)

    def write(self, lo: int, block: np.ndarray) -> None:
        self.out[lo:lo + len(block)] = block

    def finish(self) -> np.ndarray:
        return self.out


class ShardedLanding:
    """Per-device contiguous row blocks, shipped to devices as they fill.

    Rows [d * n_pad/D, (d+1) * n_pad/D) land on device d of the 1-D data
    mesh (the contiguous split NamedSharding(P(axis, None)) induces).
    Rows past `num_rows` are zero padding — masked out by the grower's
    row weights, exactly as the host-padded path does.
    """

    def __init__(self, num_rows: int, num_groups: int, dtype,
                 layout: RowLayout, mesh=None, axis: str = "data"):
        import jax

        if mesh is None:
            from ..parallel import make_mesh
            mesh = make_mesh(axis_name=axis)
        self.mesh = mesh
        self.axis = axis
        self.num_rows = int(num_rows)
        self.layout = layout
        self.num_groups = int(num_groups)
        self.dtype = np.dtype(dtype)
        ndev = int(mesh.shape[axis])
        if layout.n_pad % ndev != 0:
            log.fatal("Sharded landing: n_pad %d not divisible by %d "
                      "devices" % (layout.n_pad, ndev))
        self.block_rows = layout.n_pad // ndev
        self._devices = list(np.asarray(mesh.devices).ravel())
        self._current: Optional[np.ndarray] = None
        self._current_d = -1
        self._shards: List = [None] * ndev
        self._jax = jax

    def _block(self, d: int) -> np.ndarray:
        if self._current_d != d:
            if self._current_d >= 0:
                self._ship(self._current_d)
            self._current = np.zeros((self.block_rows, self.num_groups),
                                     self.dtype)
            self._current_d = d
        return self._current

    def _ship(self, d: int) -> None:
        with telemetry.span("ingest/device_put"):
            self._shards[d] = self._jax.device_put(self._current,
                                                   self._devices[d])
        telemetry.counter_add("ingest/device_blocks", 1)
        self._current = None
        self._current_d = -1

    def write(self, lo: int, block: np.ndarray) -> None:
        """Rows arrive in order; a chunk may straddle device blocks."""
        off = 0
        while off < len(block):
            d = (lo + off) // self.block_rows
            blk = self._block(d)
            local = (lo + off) - d * self.block_rows
            take = min(len(block) - off, self.block_rows - local)
            blk[local:local + take] = block[off:off + take]
            off += take

    def finish(self):
        if self._current_d >= 0:
            self._ship(self._current_d)
        for d in range(len(self._shards)):
            if self._shards[d] is None:  # all-padding tail block
                self._shards[d] = self._jax.device_put(
                    np.zeros((self.block_rows, self.num_groups),
                             self.dtype), self._devices[d])
        from jax.sharding import NamedSharding, PartitionSpec as P
        sharding = NamedSharding(self.mesh, P(self.axis, None))
        return self._jax.make_array_from_single_device_arrays(
            (self.layout.n_pad, self.num_groups), sharding, self._shards)
