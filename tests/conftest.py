import os
import sys

# Tests run on the single real CPU device (the 512-device override is ONLY
# for the dry-run entry point, per the assignment).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

