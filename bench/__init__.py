"""On-chip benchmark of the served spike path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the TPU it is started on. Every
configuration, traffic mix and per-layer metric is a file of its own under
this directory, found by the name the manifest gives it:

- ``configs/<config>.json``   a deployment: network, neuron model, the checks
                              ``correct`` compares, fixed point, engine
- ``networks/<kind>.py``      builds a network's float weights from the seed
- ``neurons/<kind>.py``       a neuron model: its plain NumPy reference and
                              its translation into the program's deployment
- ``traffic/<traffic>.json``  a traffic mix: parameters only
- ``drivers/<driver>.py``     the general generator a mix names
- ``metrics/<metric>.py``     reads one per-layer metric from the traced run

The yardstick lives here too, so the program cannot move it: the plain
references (``neurons/``) and the comparison (``reference.py``), the
trace reduction (``xtrace.py``), the table of peaks (``peaks.py``) and the
operation and byte counts of the minimum work (``work.py``).
"""
