"""One module per configuration ``kind``: each sets up, warms up, measures
and checks one cell (``run(cell, ...) -> bench.Outcome``)."""
