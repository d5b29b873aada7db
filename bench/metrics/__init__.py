"""One reader per per-layer metric: ``bench/metrics/<metric>.py`` defines
``read(run)``, where ``run`` holds the cell, its configuration and traffic,
the chip's peaks and the runner's record (``out``, with the reduced trace
under ``out["trace"]``).  A reader that finds nothing to read returns None
and the metric is left out of the result line."""
