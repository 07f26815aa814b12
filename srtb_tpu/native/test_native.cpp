// Sanitizer-instrumented harness for the native layer.
//
// The reference compiles its CTest suite with (commented-in) ASan flags
// and relies on in-kernel asserts + allocator diagnostics for memory
// bugs (SURVEY.md §5.2).  The TPU build's native code is this trio —
// recvmmsg receiver, AF_PACKET ring, async writer pool — so this
// harness exercises all three end-to-end under
// -fsanitize=address,undefined (built and run by `make -C
// srtb_tpu/native check`; ci.sh invokes it).  Any leak, use-after-free,
// data race on shutdown, or UB in header parsing fails the exit code.
//
// Self-contained: sends its own UDP datagrams over loopback, so it
// needs no fixture beyond CAP_NET_RAW for the ring section (skipped
// with a notice when unavailable).

#include <arpa/inet.h>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <netinet/in.h>
#include <sys/socket.h>
#include <unistd.h>

#include <string>
#include <thread>
#include <vector>

// C ABI under test (udp_receiver.cpp, packet_ring.cpp, file_writer.cpp)
extern "C" {
struct UdpRx;
UdpRx* srtb_udp_rx_create(const char*, uint16_t, uint64_t, uint64_t,
                          int32_t, int64_t);
int32_t srtb_udp_rx_receive_block(UdpRx*, uint8_t*, uint64_t, uint64_t*,
                                  uint64_t*, uint64_t*);
uint64_t srtb_udp_rx_lost_packets(UdpRx*);
void srtb_udp_rx_destroy(UdpRx*);

struct PktRing;
PktRing* srtb_pkt_ring_create(const char*, uint16_t, uint64_t, uint64_t,
                              int32_t, uint32_t, uint32_t);
int32_t srtb_pkt_ring_receive_block(PktRing*, uint8_t*, uint64_t,
                                    uint64_t*, uint64_t*, uint64_t*);
void srtb_pkt_ring_destroy(PktRing*);

struct WriterPool;
WriterPool* srtb_writer_create(int32_t, uint64_t);
int32_t srtb_writer_submit(WriterPool*, const char*, const uint8_t*,
                           uint64_t, int32_t, int32_t);
void srtb_writer_drain(WriterPool*);
uint64_t srtb_writer_bytes_written(WriterPool*);
uint64_t srtb_writer_errors(WriterPool*);
uint64_t srtb_writer_write_ns(WriterPool*);
void srtb_writer_destroy(WriterPool*);
}

// CHECK() vanishes under NDEBUG, which would turn this harness into a
// silently green gate — CHECK always executes and always aborts on
// failure, whatever the build flags.
#define CHECK(cond)                                                       \
  do {                                                                    \
    if (!(cond)) {                                                        \
      std::fprintf(stderr, "CHECK failed at %s:%d: %s\n", __FILE__,       \
                   __LINE__, #cond);                                      \
      std::abort();                                                       \
    }                                                                     \
  } while (0)

namespace {

// pid-derived ports so concurrent runs on one host don't share sockets
const uint16_t kPort = (uint16_t)(40000 + (getpid() % 2000) * 2);
constexpr size_t kHeader = 8;
constexpr size_t kPayload = 1024;
constexpr size_t kPacket = kHeader + kPayload;

void send_counters(uint16_t port, const std::vector<uint64_t>& counters) {
  int fd = socket(AF_INET, SOCK_DGRAM, 0);
  CHECK(fd >= 0);
  sockaddr_in sa{};
  sa.sin_family = AF_INET;
  sa.sin_port = htons(port);
  sa.sin_addr.s_addr = inet_addr("127.0.0.1");
  std::vector<uint8_t> pkt(kPacket);
  for (uint64_t c : counters) {
    std::memcpy(pkt.data(), &c, 8);
    std::memset(pkt.data() + kHeader, (int)(c & 0xFF), kPayload);
    (void)sendto(fd, pkt.data(), pkt.size(), 0, (sockaddr*)&sa,
                 sizeof(sa));
    usleep(2000);
  }
  close(fd);
}

int test_recvmmsg() {
  UdpRx* rx = srtb_udp_rx_create("127.0.0.1", kPort, kPacket, kHeader,
                                 /*le64*/ 0, 1 << 22);
  CHECK(rx && "bind failed");
  // loss (counter 2) + reorder (3 before 1) + overflow (4 -> next block)
  std::thread sender(send_counters, kPort,
                     std::vector<uint64_t>{0, 3, 1, 4});
  std::vector<uint8_t> out(4 * kPayload);
  uint64_t first = 0, lost = 0, total = 0;
  int rc = srtb_udp_rx_receive_block(rx, out.data(), out.size(), &first,
                                     &lost, &total);
  sender.join();
  CHECK(rc == 0 && first == 0 && total == 4 && lost == 1);
  CHECK(out[0] == 0 && out[kPayload] == 1);
  CHECK(out[2 * kPayload] == 0);  // zero-filled gap
  CHECK(out[3 * kPayload] == 3);
  CHECK(srtb_udp_rx_lost_packets(rx) == 1);
  srtb_udp_rx_destroy(rx);
  std::printf("recvmmsg: OK\n");
  return 0;
}

int test_ring() {
  PktRing* r = srtb_pkt_ring_create("lo", kPort + 1, kPacket, kHeader,
                                    /*le64*/ 0, 1 << 18, 16);
  if (!r) {
    std::printf("ring: SKIPPED (no CAP_NET_RAW)\n");
    return 0;
  }
  // hold the UDP port so the kernel does not ICMP-reject the sender
  int holder = socket(AF_INET, SOCK_DGRAM, 0);
  sockaddr_in sa{};
  sa.sin_family = AF_INET;
  sa.sin_port = htons(kPort + 1);
  sa.sin_addr.s_addr = INADDR_ANY;
  (void)bind(holder, (sockaddr*)&sa, sizeof(sa));

  std::thread sender(send_counters, kPort + 1,
                     std::vector<uint64_t>{0, 1, 2, 3, 4, 5});
  std::vector<uint8_t> out(4 * kPayload);
  uint64_t first = 0, lost = 0, total = 0;
  int rc = srtb_pkt_ring_receive_block(r, out.data(), out.size(), &first,
                                       &lost, &total);
  CHECK(rc == 0 && first == 0 && lost == 0 && total == 4);
  CHECK(out[kPayload] == 1 && out[3 * kPayload] == 3);
  // second block starts at the pending overflow packet (counter 4)
  rc = srtb_pkt_ring_receive_block(r, out.data(), 2 * kPayload, &first,
                                   &lost, &total);
  sender.join();
  CHECK(rc == 0 && first == 4 && lost == 0 && total == 2);
  CHECK(out[0] == 4 && out[kPayload] == 5);
  srtb_pkt_ring_destroy(r);
  close(holder);
  std::printf("ring: OK\n");
  return 0;
}

int test_writer() {
  char path[96];
  std::snprintf(path, sizeof(path), "/tmp/srtb_native_test_writer.%d.bin",
                (int)getpid());
  std::remove(path);
  WriterPool* w = srtb_writer_create(2, 1 << 20);
  CHECK(w);
  std::vector<uint8_t> data(4096, 0x5A);
  for (int i = 0; i < 16; i++)
    CHECK(srtb_writer_submit(w, path, data.data(), data.size(),
                              /*fsync*/ i == 15, /*append*/ 1) == 0);
  srtb_writer_drain(w);
  CHECK(srtb_writer_errors(w) == 0);
  CHECK(srtb_writer_bytes_written(w) == 16 * data.size());
  CHECK(srtb_writer_write_ns(w) > 0);  // the workers' summed write time
  srtb_writer_destroy(w);
  FILE* f = std::fopen(path, "rb");
  CHECK(f);
  std::fseek(f, 0, SEEK_END);
  CHECK(std::ftell(f) == long(16 * data.size()));
  std::fclose(f);
  std::remove(path);
  std::printf("writer: OK\n");
  return 0;
}

}  // namespace

int main() {
  // watchdog: a missed datagram must fail the gate, not hang CI
  alarm(60);
  int rc = test_writer();
  rc |= test_recvmmsg();
  rc |= test_ring();
  std::printf("native sanitizer harness: %s\n", rc ? "FAIL" : "PASS");
  return rc;
}
