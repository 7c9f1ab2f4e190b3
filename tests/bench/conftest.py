import os
import sys

# the benchmark package lives at the repository root
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
