"""On-chip benchmark of the WARP retrieval engine (``BENCHMARK.json``).

Everything here is the yardstick: traffic generation, index synthesis,
the plain reference and the comparison that decides ``correct``, the
device-trace reduction, the table of peaks and the byte and operation
counts. The program under test is reached only through its entry points
(``repro.core.Retriever``, ``repro.serving.RetrievalServer`` /
``BatchPolicy``, the ``WarpIndex`` type).

One run is ``python tpubench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.
"""
