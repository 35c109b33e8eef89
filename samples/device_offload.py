"""Device offload: annotate a query with @device to run it on the compiled
TPU path (micro-batched XLA kernels); the host interpreter remains the
fallback for shapes outside kernel coverage. Unless JAX_PLATFORMS says
otherwise this sample runs on the CPU backend, so it works anywhere — on a TPU
host, ``JAX_PLATFORMS=tpu python samples/device_offload.py`` compiles the same
code to the chip."""

import _common  # noqa: F401

from siddhi_tpu import SiddhiManager, StreamCallback

APP = """
define stream Ticks (sym string, price double);

@device(batch='64')
from Ticks[price > 10.0]#window.length(128)
select sym, sum(price) as total, count() as n
group by sym
insert into Stats;
"""

manager = SiddhiManager()
runtime = manager.create_siddhi_app_runtime(APP, playback=True)
runtime.add_callback("Stats", StreamCallback(
    lambda events: [print(f"  {e.data}") for e in events]))
runtime.start()

assert runtime.device_bridges, "query compiled onto the device path"
handler = runtime.input_handler("Ticks")
import random
rng = random.Random(7)
for i in range(256):
    handler.send([rng.choice(["a", "b"]), round(rng.uniform(0, 100), 2)],
                 timestamp=1000 + i)
runtime.flush_device()      # drain the partial micro-batch
manager.shutdown()
