"""On-chip benchmark of the planned GCN forward and graph serving.

``BENCHMARK.json`` at the checkout root names the cells; everything that
belongs to one configuration, traffic mix, per-layer metric or cell sits in
a file of its own under this directory and is found by that name:

* ``configs/<config>.json``   graph, model, plan options, what was assumed;
* ``traffic/<traffic>.json``  a traffic mix: ``kind`` plus its parameters;
* ``kinds/<kind>.py``         the generator that drives one kind of traffic;
* ``metrics/<metric>.py``     the reader of one per-layer metric;
* ``limits/<cell>.json``      the correctness limits of one cell;
* ``peaks.json``              the device peaks, keyed by ``device_kind``.

Run one cell once (from the checkout root)::

    python3 chipbench/run.py --workload gcn-reddit.full --seed 7 \
        --seconds 10 --trace 0
"""
