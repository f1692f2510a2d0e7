import sys

from benchmarks.chip.run import main

sys.exit(main())
