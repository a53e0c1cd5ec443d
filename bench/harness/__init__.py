"""The benchmark's harness: one cell of ``BENCHMARK.json`` run once.

Everything a cell needs is found by name: ``configs/<config>.json``,
``traffic/<traffic>.json`` (whose ``kind`` names the runner module in this
package), ``limits/<cell>.json`` and ``metrics/<metric>.py``.
"""
