import os
import re

# Tests must not inherit a large forced host device count — they would
# silently test the wrong topology. Small forced counts
# (<= 8) are legitimate: the population-smoke CI job runs the suite under
# --xla_force_host_platform_device_count=2 so the shard_map tests exercise
# a real multi-device mesh on the 1-CPU container.
_m = re.search(r"xla_force_host_platform_device_count=(\d+)",
               os.environ.get("XLA_FLAGS", ""))
assert _m is None or int(_m.group(1)) <= 8, (
    "run pytest without a forced host device count above 8")
