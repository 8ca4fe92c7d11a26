"""Test-suite bootstrap (shared fixtures live in the test modules)."""
