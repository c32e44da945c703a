"""Share of the IVF-PQ code scan's roofline in the ``deep10m-pq`` cell:
the work count of ``pq_scan_roofline.py`` (each distinct probed list's
codes read once, pq_dim adds a scanned row), through
``work.roofline_share``, at 8,192 lists, where each 128-query sub-tile
table holds a quarter or less of the index's units. The least time
counts the probed lists only, not the rest of a sub-tile's table."""
from benchmark import harness

read = harness.load_reader("pq_scan_roofline")
