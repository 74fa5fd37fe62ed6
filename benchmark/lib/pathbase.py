"""What every path builder shares: the lifecycle the harness drives,
the table's fill and the one program a pipeline cannot warm by
streaming."""

from __future__ import annotations

import numpy as np


class PathBase:
    """Subclasses set ``pipe``, ``source``, ``table`` and ``metrics``."""

    _stopped = False

    def fill_table(self, seed: int, plan: dict, log) -> None:
        """The table as the deployment holds it when the run starts
        (lib/prefill.py): every row written on the device from the seed
        and adopted through ``commit``, the resident keys written into
        the host mirror."""
        import jax

        from lib import prefill

        t = self.table
        t.commit(prefill.device_table(seed, t.rows, t.capacity))
        prefill.apply_fill(t, plan, log)
        jax.block_until_ready(t.values)

    def warm_renorm(self) -> None:
        """Load the table's renorm sweep (``maybe_renorm``, once every
        ~2**20 offsets, so first inside the window) as the identity:
        times one, plus zero."""
        import jax

        from flink_jpmml_tpu.compile import statekernel
        from flink_jpmml_tpu.runtime.state import STATE_WIDTH

        t = self.table
        t.values = statekernel.renorm(
            t.values, np.ones(STATE_WIDTH, np.float32),
            np.zeros(STATE_WIDTH, np.float32),
        )
        jax.block_until_ready(t.values)

    def start(self) -> None:
        self.pipe.start()

    def check_alive(self) -> None:
        self.pipe.join(timeout=0.0)  # raises what a thread raised

    def stop(self) -> None:
        """Idempotent: the harness also calls it on its way out."""
        if self._stopped:
            return
        self._stopped = True
        self.pipe.stop()
        self.pipe.join(timeout=60.0)
        self.source.close()
