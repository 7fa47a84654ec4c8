"""Test-support utilities (not imported by library code)."""
