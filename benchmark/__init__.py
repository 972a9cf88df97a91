"""The yardstick: what horovod_tpu is measured with on the chip.

``BENCHMARK.json`` at the root of the checkout lists the cells; this
package is the one harness that runs any of them
(``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``).  Everything that belongs to one configuration, one
traffic mix or one per-layer metric is a file of its own that the harness
finds by the name in the manifest, so a later PR adds a cell by adding
files and edits none that is here:

* ``configs/<name>.json``      sizes of one configuration, as run
* ``families/<family>.py``     model, loss and optimizer of a family of
                               configurations, built through ``hvd``
* ``reference/<family>.py``    its plain float32 ``jax.numpy`` reference
* ``ops/<family>.py``          operations its training requires, from shapes
* ``traffic/<name>.json``      one traffic mix, read by ``traffic.py``
* ``layer_metrics/<name>.py``  one per-layer metric's reader
* ``peaks.json``               the table of device peaks

From the program the benchmark takes only the system under test
(``horovod_tpu``), its spans, counters and kernel names.  PERF.md at the
root says why each piece is as it is.
"""
