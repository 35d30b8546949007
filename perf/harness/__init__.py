"""The benchmark's own library: found on ``sys.path`` as ``harness`` because ``perf/run.py`` is the script."""
