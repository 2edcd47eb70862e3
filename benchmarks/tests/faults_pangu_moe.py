"""The timed path of the ``pangu_moe`` family broken underneath, one
context manager for each fault (as ``faults.py`` has them for the dense
decoder). Both break what a token leaves in the latent cache, and every
row reads the cache, its own step's rows too: the rows of a prompt's
first chunk as well as every later chunk and decode step. ``test_pangu_moe.py`` drives the rest of a run over each
at a size the CPU holds; ``python3 -m benchmarks.tests.faults_pangu_moe``
does the same at the cell's own size on the chip.

    python3 -m benchmarks.tests.faults_pangu_moe --fault rope_key_unrotated \\
        --workload openpangu_ultra_moe_serve.conv_c32 --seed <n> --seconds <s>

Prints the result line of ``benchmarks.run`` and exits 0 when the fault
was caught, 1 when the run came out correct. By hand, never by the
driver."""

import contextlib
import sys

from benchmarks.tests.faults import _patched


def rope_key_unrotated():
    """The rope key is cached unrotated: the model's ``rope_rotate``
    passes the one shared key through (the queries' 128 rope parts are
    still rotated)."""
    from paddle_tpu.models import pangu_moe

    def new(real):
        def rope_rotate(x, pos, theta, interleave=False):
            return x if x.shape[2] == 1 else real(x, pos, theta, interleave)
        return rope_rotate

    return _patched(pangu_moe, "rope_rotate", new)


@contextlib.contextmanager
def latent_cached_before_norm():
    """The latent is cached before its norm ``N_kv``: the norm of every
    latent attention layer (``kv_a_layernorm``) passes its input
    through."""
    from paddle_tpu import nn
    from paddle_tpu.models.pangu_moe import PanguMLAttention

    skipped = set()             # ids of the kv_a_layernorm layers

    def attn(real):
        def forward(self, u, cache=None, paged=None):
            skipped.add(id(self.kv_a_layernorm))
            return real(self, u, cache, paged)
        return forward

    def norm(real):
        def forward(self, x, *a, **kw):
            return x if id(self) in skipped else real(self, x, *a, **kw)
        return forward

    with _patched(PanguMLAttention, "forward", attn), \
            _patched(nn.RMSNorm, "forward", norm):
        yield


FAULTS = {"rope_key_unrotated": rope_key_unrotated,
          "latent_cached_before_norm": latent_cached_before_norm}


def main(argv=None) -> int:
    """``fault_on_chip``'s route with these faults among its choices."""
    from benchmarks.tests import fault_on_chip, faults

    faults.FAULTS.update(FAULTS)
    return fault_on_chip.main(argv)


if __name__ == "__main__":
    sys.exit(main())
