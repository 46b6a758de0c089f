import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json

from repro.launch.dryrun import dryrun_one

for fname, multi in (("results/dryrun_single.json", False),
                     ("results/dryrun_multi.json", True)):
    rows = json.load(open(fname))
    for i, r in enumerate(rows):
        if r.get("shape") == "long_500k":
            rows[i] = dryrun_one(r["arch"], "long_500k", multi_pod=multi)
    json.dump(rows, open(fname, "w"), indent=1)
    print("patched", fname)
