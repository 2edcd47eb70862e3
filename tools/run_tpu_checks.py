"""Run the TPU-only test files on the chip: on-chip parity of the Pallas
kernels that the CPU suite can only interpret or compile.

This launcher never touches JAX itself (a chip belongs to one process):
it starts ONE pytest child with PADDLE_TPU_REAL_CHIP=1, which makes
tests/conftest.py leave the platform to JAX and turn the persistent
compile cache on. The normal suite collects these files too; there every
test in them skips from a fixture.

Usage (on a machine with a TPU): python tools/run_tpu_checks.py
(every file), or with the files to run as arguments.
"""

import os
import subprocess
import sys

TPU_ONLY = ["tests/test_flash_dropout_tpu.py",
            "tests/test_paged_attention_tpu.py",
            "tests/test_selective_scan_tpu.py"]

if __name__ == "__main__":
    env = dict(os.environ)
    env["PADDLE_TPU_REAL_CHIP"] = "1"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # -s: the paged-attention and selective-scan files print their
    # host-clock readings
    rc = subprocess.run([sys.executable, "-m", "pytest", "-q", "-s",
                         "-p", "no:cacheprovider",
                         *(sys.argv[1:] or TPU_ONLY)],
                        cwd=repo, env=env).returncode
    sys.exit(rc)
