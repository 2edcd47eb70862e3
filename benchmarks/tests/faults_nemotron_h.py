"""The timed path of the ``nemotron_h`` family broken underneath, one
context manager for each fault (as ``faults.py`` has them for the dense
decoder). ``test_nemotron_h.py`` drives the rest of a run over each at a
size the CPU holds; ``python3 -m benchmarks.tests.faults_nemotron_h``
does the same at the cell's own size on the chip.

    python3 -m benchmarks.tests.faults_nemotron_h --fault state_not_cleared \\
        --workload nemotron3_super_serve.decode_c64 --seed <n> --seconds <s>

Prints the result line of ``benchmarks.run`` and exits 0 when the fault
was caught, 1 when the run came out correct. By hand, never by the
driver."""

import sys

from benchmarks.tests.faults import _patched


def state_not_cleared():
    """A slot's recurrent state is not cleared on admission: the next
    request starts from what the slot's last tenant left."""
    import jax.numpy as jnp

    from paddle_tpu.models import nemotron_h

    def new(real):
        def fresh_slots(seq_lens, active):
            return jnp.zeros_like(active)
        return fresh_slots

    return _patched(nemotron_h, "fresh_slots", new)


def expert_rows_dropped():
    """One held expert's rows are dropped: every assignment to the first
    held expert is treated as not held, so its part of the routed sum is
    lost (what a capacity of nought for that expert would do)."""
    import jax.numpy as jnp

    from paddle_tpu.distributed import moe

    def new(real):
        def held_assignments(idx, live, first, n_held):
            local, held = real(idx, live, first, n_held)
            lost = local == 0
            return jnp.where(lost, n_held, local), held & ~lost
        return held_assignments

    return _patched(moe, "_held_assignments", new)


FAULTS = {"state_not_cleared": state_not_cleared,
          "expert_rows_dropped": expert_rows_dropped}


def main(argv=None) -> int:
    """``fault_on_chip``'s route with these faults among its choices."""
    from benchmarks.tests import fault_on_chip, faults

    faults.FAULTS.update(FAULTS)
    return fault_on_chip.main(argv)


if __name__ == "__main__":
    sys.exit(main())
