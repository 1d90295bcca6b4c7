"""The on-chip benchmark of ``pom.compile(target="pallas")``; see README.md."""
