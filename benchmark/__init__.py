"""The benchmark: see README.md beside this file."""
