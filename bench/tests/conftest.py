"""The benchmark's own tests run on the CPU, by path:

    python -m pytest bench/tests

They import the program from src/ and the benchmark as the `bench`
package from the checkout's root, on four CPU devices."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four CPU devices, so that cells of several replicas run here too
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                           "--xla_force_host_platform_device_count=4").strip()
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
