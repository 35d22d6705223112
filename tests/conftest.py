import os
import sys

# tests see the real single CPU device — only launch/dryrun.py forces 512.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the persistent compilation cache stays off in tests and in the CLI
# subprocesses they start (which inherit this environment)
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
