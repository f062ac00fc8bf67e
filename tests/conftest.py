# Smoke tests and benches must see 1 device, so no device-count flag here;
# multi-device tests spawn subprocesses that set it themselves.
#
# The one XLA flag set here pins XLA:CPU to AVX, which has no FMA
# instruction.  On AVX2/AVX-512 hosts LLVM contracts ``a * b + c`` into one
# fused multiply-add wherever a single fusion holds both ops, and where the
# fusion boundaries fall differs between a Pallas kernel body run by the
# interpreter and its jnp reference, and between schedule variants of one
# train step.  The BITWISE parity classes (DESIGN.md §Kernels, §Comm
# schedules) compare op sequences, so they are checked on a target where
# every multiply and add rounds on its own.  Subprocess scripts that compare
# programs bitwise append the same flag.
import os

NO_FMA_FLAG = "--xla_cpu_max_isa=AVX"

if NO_FMA_FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + NO_FMA_FLAG).strip()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-device subprocess tests (still run by default)")
