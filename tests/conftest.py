def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests (multi-device subprocesses, full sweeps)",
    )
