"""These tests run on the CPU: the chip is reached only through
``chiprun``. Set before JAX is imported by any test of this directory."""
import os
import sys

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
