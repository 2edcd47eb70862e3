"""The timed path of the ``jamba`` family broken underneath, one context
manager for each fault (as ``faults.py`` has them for the dense
decoder). ``test_jamba.py`` drives the rest of a run over each at a size
the CPU holds; ``python3 -m benchmarks.tests.faults_jamba`` does the
same at the cell's own size on the chip.

    python3 -m benchmarks.tests.faults_jamba --fault state_not_cleared \\
        --workload jamba2_3b_serve.decode_c64 --seed <n> --seconds <s>

Prints the result line of ``benchmarks.run`` and exits 0 when the fault
was caught, 1 when the run came out correct. By hand, never by the
driver."""

import sys

from benchmarks.tests.faults import _patched

# an RMS norm over fewer values than this is one of a Mamba-1 mixer's
# three (``dt_rank`` 160, ``d_state`` 16 twice); every other norm of the
# family is over the hidden size (2560; 256 in the tests' toy)
NARROW_NORM = 256


def state_not_cleared():
    """A slot's recurrent state is not cleared on admission: the next
    request starts from what the slot's last tenant left."""
    import jax.numpy as jnp

    from paddle_tpu.models import jamba

    def new(real):
        def fresh_slots(seq_lens, active):
            return jnp.zeros_like(active)
        return fresh_slots

    return _patched(jamba, "fresh_slots", new)


def norms_left_out():
    """The RMS norms on ``dt``, ``B`` and ``C`` are left out: plain
    Mamba-1, without Jamba's addition to it."""
    from paddle_tpu import nn

    def new(real):
        def forward(self, x):
            return x if x.shape[-1] < NARROW_NORM else real(self, x)
        return forward

    return _patched(nn.RMSNorm, "forward", new)


FAULTS = {"state_not_cleared": state_not_cleared,
          "norms_left_out": norms_left_out}


def main(argv=None) -> int:
    """``fault_on_chip``'s route with these faults among its choices."""
    from benchmarks.tests import fault_on_chip, faults

    faults.FAULTS.update(FAULTS)
    return fault_on_chip.main(argv)


if __name__ == "__main__":
    sys.exit(main())
